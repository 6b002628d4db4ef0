"""Model operations of the tokens processed over the seconds the server had
work at the chip's peak, in percent (chat cells)."""
from bench.harness import readers


def read(run):
    return readers.mfu(run, whole_window=False)
