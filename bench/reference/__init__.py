"""Plain float32 references the benchmark compares the served tokens with.

A configuration file names its reference module under ``"reference"``
(``bench/configs/<config>.json``); ``bench.harness.spec.load_reference``
imports ``bench/reference/<module>.py`` by that name, so a new
architecture joins the benchmark as files only.  The module imports
nothing of the program and defines:

- ``frozen(conf)``: the configuration file's dict as a hashable value,
  the ``cfg`` the functions below take (a static jit argument);
- ``gaps(weights, tokens, probe, cfg, precision)``: the reference over
  ``tokens`` (b, s) int32 with the weights the benchmark drew, returning
  ``(gap, top)``, both (b, s): how far ``probe``'s token lies below the
  reference's best logit at each position, and the reference's own top
  token.  ``precision`` is ``"f32"``, or ``"fp8"`` for the control (every
  matmul operand rounded to float8 e4m3, accumulation in float32);
- ``counts(conf)``: the least work of the architecture's steps, an object
  with ``decode_flops(rows, attended)``, ``decode_bytes(rows, attended)``
  and ``prefill_flops(cached, new)`` (``bench.harness.roofline.Counts``),
  with the argument why no implementation needs less;
- ``lower(weights, cfg, tokens)``: the programs ``gaps`` runs, lowered for
  abstract ``weights`` and a ``tokens`` shape (``jax.ShapeDtypeStruct``s
  carrying their sharding), as ``[(label, jax.stages.Lowered), ...]``, so
  that ``bench/aot_check.py`` compiles them for a described chip.
"""
