#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python bench/run.py --workload CELL --seed N --seconds S --trace 0|1

From the root of a checkout.  The cell's configuration, traffic mix,
server flags and limits are files under ``bench/`` named in
``BENCHMARK.json``.  A run draws the weights from the seed on the
device, builds the server through the program's own construction
(``repro.launch.serve.build_server``), warms every shape the traffic can
use, serves the traffic's prebuilt state, then measures for ``--seconds``
on the wall clock.  Once the window has closed, a seed-drawn sample of
the finished requests is compared with the plain reference the
configuration names (``bench/reference/<module>.py``).

Progress and counts go to standard error, ending with each number
compared beside its limit; the last line of standard output is the
result, one JSON object.  Without a TPU, or with fewer chips than the
cell asks for, the run exits 1 and prints no result.  ``--trace 1``
reports the cell's per-layer metrics from a profiler trace of a few
seconds in the middle of the window instead of its end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import Counter  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
#: seconds of the window the traced run records, centred in it
TRACE_SECONDS = 6.0
#: the fewest served tokens a run must compare
MIN_COMPARED = 64


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts the programs JAX lowers (compiled or read from the cache),
    and names those lowered while :meth:`watch` is on."""

    #: the logger that announces each lowering, at debug level
    LOGGER = "jax._src.interpreters.pxla"

    def __init__(self, jax):
        import logging
        self.n = 0
        self.seconds = 0.0
        self.names: list = []
        self._log = logging.getLogger(self.LOGGER)
        self._saved = None

        def on_duration(name, secs, **_):
            if name.startswith("/jax/core/compile/"):
                self.seconds += secs
            if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)

        counter = self

        class _Names(logging.Handler):
            def emit(self, record):
                if str(record.msg).startswith("Compiling %s with global"):
                    counter.names.append(
                        f"{record.args[0]} {record.args[1]}")
        self._handler = _Names(logging.DEBUG)

    def watch(self) -> None:
        import logging
        self._saved = (self._log.level, self._log.propagate)
        self._log.setLevel(logging.DEBUG)
        self._log.propagate = False
        self._log.addHandler(self._handler)

    def unwatch(self) -> None:
        if self._saved is not None:
            self._log.removeHandler(self._handler)
            self._log.setLevel(self._saved[0])
            self._log.propagate = self._saved[1]
            self._saved = None


def program_config(spec: dict):
    """The program's ``ModelConfig`` for a configuration file: the named
    architecture with every size the file gives.  A key whose field holds a
    sub-config (``moe``, ``ssm``, ...) takes an object of that sub-config's
    fields, applied over the architecture's own sub-config, or building one
    where it has none."""
    import dataclasses
    import typing
    from repro.configs import get_config
    from repro.configs.base import ModelConfig
    base = get_config(spec["arch"])
    hints = typing.get_type_hints(ModelConfig)
    changes = {}
    for f in dataclasses.fields(base):
        if f.name not in spec or f.name == "name":
            continue
        v = spec[f.name]
        sub = [t for t in typing.get_args(hints[f.name])
               if dataclasses.is_dataclass(t)]
        if sub and isinstance(v, dict):
            v = _sub_config(spec["name"], f.name, sub[0],
                            getattr(base, f.name), v)
        changes[f.name] = v
    cfg = dataclasses.replace(base, **changes)
    if cfg.resolved_head_dim != spec["head_dim"]:
        raise ValueError(f"{spec['name']}: head_dim {spec['head_dim']} but "
                         f"the program resolves {cfg.resolved_head_dim}")
    return cfg


def _sub_config(config: str, key: str, cls, current, given: dict):
    """``given`` over ``current`` (or a new ``cls`` where that is None),
    lists as tuples so that the config stays hashable."""
    import dataclasses
    known = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(given) - known)
    if unknown:
        raise ValueError(f"{config}: {key} has no field "
                         f"{', '.join(unknown)} (it has {sorted(known)})")
    given = {k: tuple(v) if isinstance(v, list) else v
             for k, v in given.items()}
    return (cls(**given) if current is None
            else dataclasses.replace(current, **given))


def device_info(jax, cell, peaks, require_chip: bool):
    """The devices the run uses; ``None`` where the run must stop."""
    devs = jax.devices()
    kind = devs[0].device_kind
    if require_chip:
        if devs[0].platform != "tpu":
            log(f"bench: needs a TPU, JAX found {devs[0].platform} ({kind})")
            return None
        if len(devs) < cell.chips:
            log(f"bench: {cell.name} needs {cell.chips} chips, found "
                f"{len(devs)}")
            return None
        if kind not in peaks:
            log(f"bench: no peaks for device kind {kind!r} in "
                f"bench/peaks.json")
            return None
    return devs[:cell.chips]


class Tracer:
    """Starts the profiler at ``start`` and stops it at ``stop`` (host
    clock), inside a ``window`` annotation the reduction clips to."""

    def __init__(self, jax, start: float, stop: float):
        self.jax = jax
        self.start, self.stop = start, stop
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.span: Optional[tuple] = None
        self._ann = None
        self._t0 = None

    def tick(self, now: float) -> None:
        if self._ann is None and self.span is None and now >= self.start:
            self.jax.profiler.start_trace(self.dir)
            self._ann = self.jax.profiler.TraceAnnotation("window")
            self._ann.__enter__()
            self._t0 = time.perf_counter()
        elif self._ann is not None and now >= self.stop:
            self.close()

    def close(self) -> None:
        if self._ann is None:
            return
        self._ann.__exit__(None, None, None)
        self.span = (self._t0, time.perf_counter())
        self.jax.profiler.stop_trace()
        self._ann = None

    def read(self):
        from bench.harness import trace
        try:
            return trace.read(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, after=None) -> Optional[dict]:
    """One run of ``cell``; returns the result line's object, or ``None``
    (after saying why) where the devices do not fit the cell.  ``after``,
    if given, is called as ``after(weights, sample, ref, run)`` once the
    comparison is made, and what it returns is kept under
    ``"calibration"`` (``bench/calibrate.py``)."""
    import jax
    from bench.harness import check, readers, record, spec, weights
    from bench.harness import client as clients
    from bench.harness.traffic import Traffic

    if not __import__("os").environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter(jax)
    peaks = spec.load_peaks()
    devs = device_info(jax, cell, peaks, require_chip)
    if devs is None:
        return None
    dev = devs[0]
    peak = peaks.get(dev.device_kind)
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"jax {jax.__version__}")

    from repro.core.prefix_cache import PrefixCacheConfig
    from repro.launch import serve as serve_cli
    from repro.models.params import build_schema

    conf = cell.config
    cfg = program_config(conf)
    key = weights.key_from_seed(seed)
    params = weights.draw(build_schema(cfg), jax.random.fold_in(key, 0))
    jax.block_until_ready(params)
    log(f"weights: {conf['name']} from seed {seed} "
        f"({sum(x.nbytes for x in jax.tree.leaves(params)) / 2**30:.3f} GiB)"
        f" at {time.perf_counter() - T_START:.3f} s")

    srv = cell.server
    args = serve_cli.parse_args(list(srv["flags"]) + ["--seed", str(seed)])
    if srv.get("prefix_cache_blocks"):
        args.prefix_cache = PrefixCacheConfig(
            capacity_blocks=int(srv["prefix_cache_blocks"]))
    runtime, server = serve_cli.build_server(cfg, params, args)
    traffic = Traffic(cell.traffic, seed, seconds, srv.get("rate_per_s"),
                      cfg.vocab_size)
    annotate = (jax.profiler.TraceAnnotation if trace
                else clients.no_annotation)
    client = clients.Client(server, traffic, args.max_batch,
                           annotate=annotate)
    # the prebuilt sessions first, so that the warm-up and the push-out
    # move their history to the tiers, as a returning user's would be
    client.prebuild()
    client.warm(traffic.prompt_lengths())
    if srv.get("prefix_cache_blocks") and traffic.prebuilt:
        client.push_out(traffic.prompt_lengths()[-1])
        client.probe_reload()
    eng = server.engine
    jax.block_until_ready((eng.pool_k, eng.pool_v))
    kv0 = Counter(eng.kv_mgr.stats)
    pre0 = eng.stats.preemptions
    c0 = compiles.n
    setup_s = time.perf_counter() - T_START
    log(f"set-up: {setup_s:.3f} s ({compiles.seconds:.3f} s compiling or "
        f"reading the cache, {c0} programs); warmed prompt lengths "
        f"{traffic.prompt_lengths()[0]}..{traffic.prompt_lengths()[-1]}, "
        f"{len(traffic.prebuilt)} prebuilt sessions; tier moves in set-up: "
        + ", ".join(f"{k} {kv0.get(k, 0)}" for k in (
            "evict_to_peer", "evict_to_host", "reload_peer", "reload_host")))

    tracer = None
    if trace:
        mid = seconds / 2
        half = min(TRACE_SECONDS, seconds) / 2
        start = time.perf_counter() + mid - half
        tracer = Tracer(jax, start, start + 2 * half)
    compiles.watch()
    t0, t_end = client.run(seconds, on_tick=tracer.tick if tracer else None)
    compiles.unwatch()
    in_window = compiles.n - c0
    kv = Counter(eng.kv_mgr.stats)
    kv.subtract(kv0)
    preempted = eng.stats.preemptions - pre0
    tr = None
    if tracer is not None:
        tracer.close()
        tr = tracer.read()
    mem = dev.memory_stats() or {}
    peak_bytes = mem.get("peak_bytes_in_use")

    run = record.Run(
        seconds=seconds, t0=t0, t_end=t_end, setup_s=setup_s,
        max_batch=args.max_batch, counts=cell.reference.counts(conf),
        peak=peak,
        turns=client.turns, prefills=client.prefills,
        decodes=client.decodes, steps=client.steps, hook_s=client.hook_s,
        hook_blocks=client.hook_blocks, kv=dict(kv), trace=tr,
        traced=tracer.span if tracer else None)
    metrics = {}
    for m in cell.metrics_for(trace):
        v = m.read(run)
        if v is None:
            # BENCHMARK.json lists the metric for this cell: a run that
            # reads nothing for it is at fault, not quiet
            raise RuntimeError(f"metric {m.name}, listed for {cell.name}, "
                               f"read nothing in this run")
        metrics[m.name] = {"value": v, "unit": m.unit}
    due = run.due
    rejected = sum(1 for t in due if t.handle.state == "rejected")
    lag = readers.client_lag_ms(run, 99)
    log(f"window: {seconds} s, {len(due)} requests due, "
        f"{sum(t.finished and run.inside(t.times[-1]) for t in client.turns)}"
        f" finished, {sum(len(t.times) for t in client.turns)} tokens in all")
    log("tier moves in the window: " + ", ".join(
        f"{k} {kv.get(k, 0)}" for k in ("evict_to_peer", "evict_to_host",
                                        "reload_peer", "reload_host"))
        + f"; preemptions {preempted}")
    log(f"compiles inside the window: {in_window}"
        + "".join(f"\n  compiled in the window: {n}"
                  for n in compiles.names))
    log(f"client lag p99: " + (f"{lag:.3f} ms" if lag is not None else
                               "no request"))
    log("peak HBM: " + (f"{peak_bytes} B ({peak_bytes / 2**30:.3f} GiB)"
                        if peak_bytes is not None else "not reported"))

    # ---- correctness, after the window, with the program's state freed
    width = 128 * -(-traffic.max_sequence() // 128)
    done = [t for t in client.turns if t.finished and run.inside(t.times[-1])]
    sample = check.draw_sample(done, seed, width)
    client.server = client.eng = None
    runtime = server = eng = client = None
    gc.collect()
    ref = check.Reference.of(cell)
    t_ref = time.perf_counter()
    gap = check.widest_gap(params, sample, ref)
    log(f"reference: {sample.requests} requests, {sample.served_tokens} "
        f"served tokens in {time.perf_counter() - t_ref:.3f} s")
    limit = float(srv["checks"]["logit_gap"])
    correct = (sample.served_tokens >= MIN_COMPARED and gap <= limit
               and rejected == 0)
    checks = {
        "logit_gap": {"value": gap, "limit": limit},
        "tokens_compared": {"value": sample.served_tokens,
                            "limit": MIN_COMPARED},
    }
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": len(due),
              "failed": rejected, "metrics": metrics, "device": device}
    if tr is not None:
        from bench.harness import trace as T
        device["busy_s"] = T.busy_s(tr)
        device["window_s"] = tr.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in T.op_seconds(tr)[:10]],
            "idle_gaps": [[n, s] for n, s in T.idle_gaps(tr)[:10]]}
    if after is not None:
        result["calibration"] = after(params, sample, ref, run)
    result["checks"] = checks
    log(f"check logit_gap {gap!r} limit {limit!r} (at most)")
    log(f"check tokens_compared {sample.served_tokens} limit "
        f"{MIN_COMPARED} (at least)")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    if not (ROOT / "src" / "repro").is_dir():
        log(f"bench: no program beside the benchmark (no {ROOT / 'src'})")
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.harness import spec
    cell = spec.load_cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    if result is None:
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
