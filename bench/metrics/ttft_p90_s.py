"""90th percentile over the requests due in the window of the wait from
when each was due to its first token, in seconds."""
from bench.harness import readers


def read(run):
    return readers.ttft_s(run, 90)
