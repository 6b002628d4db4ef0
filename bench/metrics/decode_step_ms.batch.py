"""Device milliseconds per run of the decode program, from the trace (batch
cell)."""
from bench.harness import readers


def read(run):
    return readers.decode_step_ms(run)
