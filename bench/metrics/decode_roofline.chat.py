"""Roofline share of the decode program: least time from its operations and
bytes over its device time, in percent (chat cells)."""
from bench.harness import readers


def read(run):
    return readers.decode_roofline(run)
