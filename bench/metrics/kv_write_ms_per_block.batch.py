"""Host milliseconds per ``kv.write`` span, the eager write of one
block's K and V into the pools, over the traced span (batch cells)."""
from bench.harness import spans


def read(run):
    return spans.read(run, spans.kv_write_ms_per_block)
