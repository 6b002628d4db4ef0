"""Roofline share of the decode program given the routes each traced step
reported: least time over device time, in percent (MoE batch cell)."""
from bench.harness import routes


def read(run):
    return routes.decode_roofline(run)
