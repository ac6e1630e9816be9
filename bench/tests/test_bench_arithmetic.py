"""Percentiles, rates, MFU and roofline arithmetic on fixed inputs, read
through the metric files exactly as a run reads them."""
import json

import pytest

from tinytree import BENCH, PEAKS
from harness import stats
from harness.cell import Run
from harness.roofline import ModelCost, least_time
from harness.serve import Call, Req
from harness.spec import Cell

QWEN = json.loads((BENCH / "configs" / "qwen3-0.6b.json").read_text())


def test_percentile_and_rates():
    assert stats.percentile([], 95) is None
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3.0
    assert stats.percentile(list(range(101)), 95) == pytest.approx(95.0)
    assert stats.percentile([0.0, 10.0], 95) == pytest.approx(9.5)
    assert stats.rate(300, 30.0) == 10.0
    assert stats.share(1, 4) == 25.0
    assert stats.share(1, 0) is None


def test_qwen3_matmul_flops_and_kv_bytes():
    c = ModelCost.from_config(QWEN)
    # q, k, v, o and a gated MLP: 1024*2048*2 + 1024*1024*2 + 3*1024*3072
    assert c.layer_matmul_params == 15728640
    assert c.dense_flops(1) == 2 * (28 * 15728640 + 1024 * 151936)
    assert c.attn_flops(100) == 4 * 100 * 16 * 128 * 28
    flops, nbytes = c.decode_attn([10, 30])
    assert flops == 4 * 40 * 16 * 128 * 28
    # float32 pool: 40 tokens x (k + v) x 8 kv heads x 128 x 4 bytes, plus
    # q and o of 2 slots x 16 heads x 128 in bfloat16, in every layer
    assert nbytes == (40 * 2 * 8 * 128 * 4 + 2 * 2 * 16 * 128 * 2) * 28
    # a causal prompt of n tokens attends 1 + 2 + ... + n keys
    assert c.prompt_flops(3) == c.dense_flops(3) + c.attn_flops(6)


def test_least_time_takes_the_larger_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert least_time(1000, 10, peaks) == 10.0
    assert least_time(100, 50, peaks) == 5.0


@pytest.fixture
def run():
    cell = Cell(BENCH.parent, "qwen3-0.6b.docqa")
    reqs = [Req(uid=i, sched=float(i), submitted=float(i) + 0.001, plen=10,
                max_tokens=5, prompt=None, first=float(i) + 0.1 * (i + 1),
                finish=float(i) + 0.1 * (i + 1) + 0.04 * (i + 1),
                admitted=float(i) + 0.01 * (i + 1),
                tokens=[1] * 5, in_window=5 if i < 8 else 2)
            for i in range(10)]
    calls = [Call("prefill", 1.0, 1.5, flops=2e9, rows=2),
             Call("decode", 2.0, 2.01, flops=1e9, kernel=(1e6, 4.1e6), rows=4),
             Call("decode", 3.0, 3.03, flops=3e9, kernel=(1e6, 8.2e6), rows=4),
             Call("decode", 11.0, 11.5, flops=9e9, rows=4)]
    return Run(cell=cell, cost=ModelCost.from_config(QWEN), peaks=PEAKS,
               setup_s=12.5, t0=0.0, t1=10.0, reqs=reqs, calls=calls,
               drain_end=12.0, chips_used=1)


def test_end_to_end_readers(run):
    cell = run.cell
    assert cell.reader("setup_s")(run) == 12.5
    ttft = [0.1 * (i + 1) for i in range(10)]
    assert cell.reader("ttft_p50_s")(run) == pytest.approx(
        stats.percentile(ttft, 50))
    # (finish - first) / (5 - 1) = 10 ms x (i + 1)
    assert cell.reader("tpot_p95_ms")(run) == pytest.approx(95.5)
    assert cell.reader("output_tok_s")(run) == pytest.approx(
        (8 * 5 + 2 * 2) / 10.0)


def test_layer_readers(run):
    cell = run.cell
    assert cell.reader("queue_wait_p95_ms")(run) == pytest.approx(95.5)
    assert cell.reader("prefill_call_ms")(run) == pytest.approx(500.0)
    # the decode call at 11 s lies outside the window
    assert cell.reader("decode_step_ms")(run) == pytest.approx(20.0)
    assert cell.reader("decode_mfu")(run) == pytest.approx(
        100 * 4e9 / (0.04 * PEAKS["bf16_flops"]))
    assert cell.reader("prefill_mfu")(run) == pytest.approx(
        100 * 2e9 / (0.5 * PEAKS["bf16_flops"]))
    assert cell.reader("pipeline_tick_ms")(run) is None
    # no trace: the device readers find nothing and say so
    for name in ("device_idle_share", "paged_decode_roofline"):
        assert cell.reader(name)(run) is None
