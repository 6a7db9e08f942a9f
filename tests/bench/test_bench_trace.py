"""The reduction from a profiler trace to the per-layer metrics."""
import json
import pathlib

import pytest

from bench import trace as tracelib
from bench.metrics import device_idle_share, quant_aggregate_roofline, \
    step_mfu

DATA = pathlib.Path(__file__).resolve().parent / "data"


def handmade():
    # two calls: host [0, 100) and [120, 200) ns; device ops inside them
    host = [["bench_chunk", 0, 100], ["PjitFunction(f)", 10, 30],
            ["bench_chunk", 120, 80], ["finish", 170, 25]]
    # a while op encloses its body's ops, as on a TPU's "XLA Ops" line
    dev = [["%while.3 = (f32[4]) while(...)", 20, 50],
           ["%fusion.1 = f32[4] fusion(...)", 20, 25],
           ["%slice.4 = s8[3,256]{1,0:S(1)} slice(...)", 45, 5],
           ["%quant_aggregate.9 = f32[8,256] custom-call(f32[3] %p.1, "
            "s8[3,256]{1,0:S(1)} %slice.4)", 50, 10],
           ["%fusion.1 = f32[4] fusion(...)", 60, 10],
           ["%copy.2 = f32[4] copy(...)", 130, 40]]
    return {"devices": [{"plane": "/device:TPU:0", "events": dev}],
            "host": host}


def test_reduce_window_busy_ops_and_gaps():
    r = tracelib.reduce(handmade())
    assert r["window_s"] == pytest.approx(200e-9)
    # union: [20, 70) + [130, 170) = 90 ns busy
    assert r["busy_s"] == pytest.approx(90e-9)
    ops = dict(r["device_ops"])
    assert ops["%fusion.1 = f32[4]"] == pytest.approx(35e-9)
    assert ops["%while.3 = (f32[4])"] == pytest.approx(0.0)
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    assert r["op_calls"]["quant_aggregate"] == [1, pytest.approx(10e-9)]
    assert r["op_calls"]["fusion"][0] == 2
    # the kernel's call carries the slice that staged its operand in VMEM
    assert r["kernels"]["quant_aggregate"] == [1, pytest.approx(15e-9)]
    gaps = dict(r["idle_gaps"])
    # [0,20) mid 10 -> PjitFunction (innermost); [70,130) mid 100 -> between
    # the calls; [170,200) mid 185 -> finish
    assert gaps["PjitFunction(f)"] == pytest.approx(20e-9)
    assert gaps["outside the calls"] == pytest.approx(60e-9)
    assert gaps["finish"] == pytest.approx(30e-9)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_reduce_finds_nothing_without_device_ops():
    c = handmade()
    c["devices"][0]["events"] = []
    assert tracelib.reduce(c) is None


def ctx_for(reduced, cell, shape):
    return {"trace": reduced, "spans": [], "window_calls": 2, "cell": cell,
            "shape": shape, "peaks": {"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9}}


def test_readers_on_the_handmade_trace():
    from bench import cells
    cell = cells.load_cell("mlp-int8-xdevice")
    ctx = ctx_for(tracelib.reduce(handmade()), cell, cells.shape(cell))
    assert device_idle_share.read(ctx) == pytest.approx(55.0)
    assert step_mfu.read(ctx) > 0
    share = quant_aggregate_roofline.read(ctx)
    # 104,177,920 bytes at 819 GB/s in a 10 ns call staged in 5 ns
    assert share == pytest.approx(100 * 104_177_920 / 819e9 / 15e-9)
    ctx["trace"] = None
    assert all(m.read(ctx) is None for m in
               (device_idle_share, step_mfu, quant_aggregate_roofline))


def test_recorded_chip_trace():
    """A slice of a traced mlp-int8-xdevice window on one v5e chip."""
    path = DATA / "trace_mlp_int8_v5e.json"
    compact = json.loads(path.read_text())
    r = tracelib.reduce(compact)
    rec = compact["expected"]
    assert r["chunks"] == rec["chunks"]
    assert r["busy_s"] == pytest.approx(rec["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(rec["window_s"], rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    calls = sum(c for n, (c, _) in r["op_calls"].items()
                if "quant_aggregate" in n)
    assert calls == rec["kernel_calls"]
