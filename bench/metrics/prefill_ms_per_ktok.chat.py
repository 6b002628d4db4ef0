"""Device milliseconds of the prefill program per 1,000 padded positions,
from the trace (chat cells)."""
from bench.harness import readers


def read(run):
    return readers.prefill_ms_per_ktok(run)
