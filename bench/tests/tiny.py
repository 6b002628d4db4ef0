"""A cell at a size a CPU test can hold: the cell's own files, with the
model cut to two layers of width 1024 (the configuration's head size, a
quarter of its heads) and a 2,048-token vocabulary, prompts of 64–256
positions and answers of 64, and a peer budget of a few blocks so that
the tiers still move blocks."""
from __future__ import annotations

from typing import Optional

from bench.harness import spec

TINY = dict(num_layers=2, d_model=1024, num_heads=8, d_ff=1024,
            vocab_size=2048)


def tiny_cell(name: str, benchmark: Optional[dict] = None):
    """The cell ``name`` of ``benchmark`` (default: ``BENCHMARK.json``),
    cut to the tiny size."""
    cell = spec.load_cell(name, benchmark)
    cell.config = dict(cell.config, **TINY,
                       num_kv_heads=max(1, cell.config["num_kv_heads"] // 4),
                       sliding_window=64 if cell.config.get("sliding_window")
                       else None)
    cell.traffic = dict(
        cell.traffic, answer=dict(median=64, sigma=0.0, min=64, max=64),
        prompt=dict(median=128, sigma=0.45, min=64, max=256))
    flags = list(cell.server["flags"])
    flags[flags.index("--peer-budget-mb") + 1] = "0.25"
    cell.server = dict(cell.server, flags=flags)
    return cell


def run_tiny(name: str, seed: int, seconds: float = 15.0, **kw):
    from bench.run import run_cell
    return run_cell(tiny_cell(name), seed, seconds, False,
                    require_chip=False, **kw)
