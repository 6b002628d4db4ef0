"""99th percentile of the gaps between consecutive tokens of a request,
over every gap inside the window, in milliseconds."""
from bench.harness import readers


def read(run):
    return readers.itl_ms(run, 99)
