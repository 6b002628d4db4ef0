"""Mean routes per held expert per traced decode step (MoE batch cell)."""
from bench.harness import routes


def read(run):
    return routes.routes_per_expert(run)
