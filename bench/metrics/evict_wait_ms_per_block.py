"""Milliseconds of ``device.wait`` inside ``kv.evict`` spans per block
evicted in the traced span: an eviction's read of its block waiting for
the device."""
from bench.harness import spans


def read(run):
    return spans.read(run, spans.evict_wait_ms_per_block)
