"""Mean decoding rows per decode step over max_batch (scheduler and step
loop, batch cell)."""
from bench.harness import readers


def read(run):
    return readers.batch_occupancy(run)
