"""A second reference module, for ``test_reference_dispatch.py``: the dense
reference, counting each call the harness makes of it."""
from collections import Counter

from bench.reference import llama

frozen = llama.frozen
lower = llama.lower
#: ("gaps", precision) and "counts" -> calls since the last clear
calls: Counter = Counter()


def gaps(weights, tokens, probe, cfg, precision="f32"):
    calls["gaps", precision] += 1
    return llama.gaps(weights, tokens, probe, cfg, precision)


def counts(conf):
    calls["counts"] += 1
    return llama.counts(conf)
