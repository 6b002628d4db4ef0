"""Model operations of the traced span's prefills and decode steps, with the
expert work of the routes they reported, at the chip's peak, in percent
(MoE batch cell)."""
from bench.harness import routes


def read(run):
    return routes.mfu(run)
