"""Model operations of the tokens processed over the whole window at the
chip's peak, in percent (batch cell)."""
from bench.harness import readers


def read(run):
    return readers.mfu(run, whole_window=True)
