"""Share of the traced window with no operation on the device, in percent
(chat cells)."""
from bench.harness import readers


def read(run):
    return readers.device_idle_share(run)
