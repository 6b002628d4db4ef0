"""The busiest held expert's routes over the held experts' mean, per traced
decode step and layer, averaged (MoE batch cell)."""
from bench.harness import routes


def read(run):
    return routes.load_max_over_mean(run)
