"""The control at a size a test run can hold: the reference computed in
float8 e4m3 put in the program's place must come out not correct under
each cell's limit, while the program itself stays inside it."""
import json

import pytest

from bench.harness import check, spec
from bench.tests.tiny import run_tiny

CELLS = [w["name"] for w in json.load(open(spec.ROOT / "BENCHMARK.json"))
         ["workloads"]]


def _control(weights, sample, ref, _run):
    top = check.control_tokens(weights, sample, ref)
    return check.widest_gap(weights, sample, ref, probe=top)


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit(cell):
    r = run_tiny(cell, 2**31 + 41, after=_control)
    limit = r["checks"]["logit_gap"]["limit"]
    assert r["correct"], r["checks"]
    assert r["calibration"] > limit, (r["calibration"], limit)
