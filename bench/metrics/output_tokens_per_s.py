"""Output tokens delivered inside the window over its seconds."""
from bench.harness import readers


def read(run):
    return readers.output_tokens_per_s(run)
