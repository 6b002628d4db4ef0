"""Percent of the traced span's ``engine.step`` time covered by
``engine.prefill`` spans (chat cells)."""
from bench.harness import spans


def read(run):
    return spans.read(run, spans.prefill_share)
