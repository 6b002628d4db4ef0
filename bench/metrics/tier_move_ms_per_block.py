"""Milliseconds inside the engine's evict and reload hooks per block moved
in the window; the traced run waits for the pools at each hook's exit."""
from bench.harness import readers


def read(run):
    return readers.tier_move_ms_per_block(run)
