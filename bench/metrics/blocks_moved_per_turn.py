"""Evictions and reloads over peer and host tiers in the window, per turn
finished in it."""
from bench.harness import readers


def read(run):
    return readers.blocks_moved_per_turn(run)
