"""The traffic generator: deterministic per seed, the same work for every
seed, and the lengths and arrivals its file asks for."""
import json

import numpy as np
import pytest

from tinytree import BENCH  # noqa: F401  (puts bench/ on the path)
from harness import gen

CHAT = json.loads((BENCH / "traffic" / "chat.json").read_text())
DOCQA = json.loads((BENCH / "traffic" / "docqa.json").read_text())
PIPE = json.loads((BENCH / "traffic" / "pipeline4.json").read_text())


def _key(items):
    return [(it.at_s, it.max_tokens, it.prompt.tobytes()) for it in items]


@pytest.mark.parametrize("traffic", [CHAT, DOCQA, PIPE])
def test_same_seed_same_requests(traffic):
    a = gen.make_items(traffic, 2 ** 31 + 11, 30.0, 1000)
    b = gen.make_items(traffic, 2 ** 31 + 11, 30.0, 1000)
    assert a and _key(a) == _key(b)


@pytest.mark.parametrize("traffic", [CHAT, DOCQA, PIPE])
def test_seeds_share_the_work_in_another_order(traffic):
    a = gen.make_items(traffic, 1, 30.0, 1000)
    b = gen.make_items(traffic, 2, 30.0, 1000)
    assert [it.at_s for it in a] == [it.at_s for it in b]
    assert sorted(len(it.prompt) for it in a) == \
        sorted(len(it.prompt) for it in b)
    assert sorted(it.max_tokens for it in a) == \
        sorted(it.max_tokens for it in b)
    assert [len(it.prompt) for it in a] != [len(it.prompt) for it in b]


def test_lognormal_lengths():
    spec = {"dist": "lognormal", "median": 200, "sigma": 0.8,
            "min": 16, "max": 1024}
    x = gen.lengths(spec, 2001)
    assert x.min() >= 16 and x.max() <= 1024
    assert abs(np.median(x) - 200) <= 1
    # the 84th percentile of a lognormal sits one sigma above the median
    assert abs(np.percentile(x, 84.13) / 200 - np.exp(0.8)) < 0.02


def test_uniform_lengths():
    x = gen.lengths({"dist": "uniform", "min": 16, "max": 64}, 4900)
    assert x.min() == 16 and x.max() == 64
    counts = np.bincount(x)[16:]
    assert counts.min() == counts.max() == 100


@pytest.mark.parametrize("process", ["poisson", "mmpp"])
def test_arrival_rate(process):
    spec = {"process": process, "rate_rps": 5.0, "burst_factor": 8.0,
            "p_enter": 0.05, "p_exit": 0.15, "shape_seed": 3}
    at = gen.arrivals(spec, 4000.0)
    assert np.all(np.diff(at) > 0) and at[-1] < 4000.0
    assert abs(len(at) / 4000.0 - 5.0) < 0.35


def test_mmpp_is_burstier_than_poisson():
    base = {"rate_rps": 5.0, "burst_factor": 8.0, "p_enter": 0.05,
            "p_exit": 0.15, "shape_seed": 3}
    cv = {}
    for p in ("poisson", "mmpp"):
        gaps = np.diff(gen.arrivals(dict(base, process=p), 4000.0))
        cv[p] = gaps.std() / gaps.mean()
    assert abs(cv["poisson"] - 1.0) < 0.1
    assert cv["mmpp"] > 1.15


def test_closed_loop_pool_and_tokens():
    items = gen.make_items(PIPE, 5, 30.0, 49152)
    assert len(items) == PIPE["requests"]
    assert all(it.at_s == 0.0 for it in items)
    assert all(1 <= t < 49152 for it in items for t in it.prompt)
    assert gen.longest(PIPE) == 256 + 256 - 1
