"""99th percentile of how late the client sent the requests due in the
window, in milliseconds (front door, chat cells)."""
from bench.harness import readers


def read(run):
    return readers.client_lag_ms(run, 99)
