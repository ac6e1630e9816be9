"""The controls at a size a test run can hold: the reference put in the
program's place at int8 and at float8 must each fail a number that sound
runs of the program pass.  On a wider toy model (CPU, nine seeds) the
program's widest logit gap read at most 0.045 and its mean gap at most
0.00085; float8's widest gap read at least 0.36, int8's mean gap at least
0.0020 (its widest gap only 0.09, too close to the program's to be
compared).  The toy cell's limits sit between: 0.15 and 0.0013."""
import pytest

from tinytree import WIDER, make, run
from harness import check

LIMITS = {"logit_gap": 0.15, "mean_gap": 0.0013}
#: the number each control has to fail
FAILS = {"fp8": "logit_gap", "int8": "mean_gap"}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make(tmp_path_factory.mktemp("control"), sizes=WIDER,
                sample_tokens=200, **LIMITS)


@pytest.mark.parametrize("seed", [1, 4])
def test_program_passes_and_control_fails(tree, seed):
    def probe(c, s, picked, rec):
        return {p: check.stats(check.control_gaps(c, s, picked, p))
                for p in FAILS}

    r = run(tree, "tiny.chat", seed=seed, probe=probe)
    assert r["correct"], r["checks"]
    for p, number in FAILS.items():
        assert r["checks"][number]["value"] <= LIMITS[number] \
            < r["probe"][p][number], (p, r["probe"][p])
