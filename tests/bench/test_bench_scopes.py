"""Device time by ``fl.*`` scope and idle time by ``fl.*`` span
(bench/scopes.py), the span readers (bench/spans.py), and the reduction of
bench/trace.py on its recorded chip trace as it was first computed."""
import json
import pathlib

import pytest

from bench import scopes, spans
from bench import trace as tracelib
from bench.metrics import aggregate_ms, cohort_plan_ms, local_train_ms, \
    metrics_pull_ms, pack_ms, slab_stage_ms

DATA = pathlib.Path(__file__).resolve().parent / "data"


def handmade():
    # one call [0, 200) ns: the cohort plan holds the device idle at first,
    # then a while op whose body runs the local step and the aggregation
    host = [["bench_chunk", 0, 200], ["fl.chunk", 0, 200],
            ["fl.launch", 5, 180], ["fl.stage_slab", 5, 60],
            ["fl.cohort_plan", 10, 40], ["PjitFunction(argsort)", 20, 10],
            ["fl.dispatch", 65, 10], ["fl.device_wait", 75, 100],
            ["fl.finish_chunk", 185, 10]]
    dev = [["%while.3 = (f32[4]) while(...)", 70, 80],
           ["%fusion.1 = f32[4] fusion(...)", 70, 40],
           ["%copy.2 = f32[4] copy(...)", 110, 10],
           ["%quant_aggregate.9 = f32[8,256] custom-call(...)", 120, 20],
           ["%fusion.7 = f32[4] fusion(...)", 160, 10]]
    stacks = [None, "fl.local_train", None, "fl.aggregate", "fl.pack"]
    return {"devices": [{"plane": "/device:TPU:0", "events": dev,
                         "scopes": stacks}], "host": host}


def test_reduce_scopes_and_idle_spans():
    c = handmade()
    r = scopes.reduce(c)
    got = dict(r["scopes"])
    # while: 80 - (40 + 10 + 20) = 10 ns of its own, outside every scope
    assert got == pytest.approx({"fl.local_train": 40e-9, "other": 20e-9,
                                 "fl.aggregate": 20e-9, "fl.pack": 10e-9})
    busy = tracelib.reduce(c)["busy_s"]
    assert sum(got.values()) == pytest.approx(busy)
    idle = dict(r["idle_spans"])
    # [0,70) mid 35 -> fl.cohort_plan (the PjitFunction is not a span);
    # [150,160) mid 155 -> fl.device_wait; [170,200) mid 185 ->
    # fl.finish_chunk
    assert idle == pytest.approx({"fl.cohort_plan": 70e-9,
                                  "fl.device_wait": 10e-9,
                                  "fl.finish_chunk": 30e-9})
    assert sum(idle.values()) + busy == pytest.approx(200e-9)


def test_reduce_labels_what_no_span_covers():
    c = handmade()
    c["host"] = [h for h in c["host"] if h[0] in ("bench_chunk",
                                                  "PjitFunction(argsort)")]
    r = scopes.reduce(c)
    assert set(dict(r["idle_spans"])) == {scopes.OUTSIDE}
    del c["devices"][0]["scopes"]
    assert dict(scopes.reduce(c)["scopes"]) == pytest.approx(
        {scopes.OTHER: 90e-9})


def test_reduce_finds_nothing_without_device_ops_or_calls():
    c = handmade()
    c["devices"][0]["events"] = []
    assert scopes.reduce(c) is None
    c = handmade()
    c["host"] = c["host"][1:]
    assert scopes.reduce(c) is None


@pytest.mark.parametrize("stack,scope", [
    ("jit(<lambda>)/while/body/closed_call/vmap(fl.local_train)/"
     "transpose(jvp())/dot_general:", "fl.local_train"),
    ("jit(<lambda>)/vmap(fl.local_train)/fl.pack/abs", "fl.pack"),
    ("fl.aggregate/fl.aggregate/add", "fl.aggregate"),
    ("jit(<lambda>)/while:", None), ("", None), (None, None)])
def test_scope_is_the_innermost_fl_component(stack, scope):
    assert scopes.scope_of(stack) == scope


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if n:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def _f(field, value):
    """One protobuf field: an int as a varint, bytes length-delimited."""
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _xspace():
    stat_meta = (_f(5, _f(1, 7) + _f(2, _f(1, 7) + _f(2, b"tf_op")))
                 + _f(5, _f(1, 8) + _f(2, _f(1, 8) + _f(2, b"flops"))))
    event_meta = (
        _f(4, _f(1, 1) + _f(2, _f(1, 1) + _f(2, b"%fusion.1")
                              + _f(5, _f(1, 8) + _f(3, 99))
                              + _f(5, _f(1, 7)
                                   + _f(5, b"jit(f)/fl.pack/abs"))))
        + _f(4, _f(1, 2) + _f(2, _f(1, 2) + _f(2, b"%copy.2"))))
    ops = _f(2, b"XLA Ops") + _f(3, 1000) + b"".join(
        _f(4, _f(1, m) + _f(2, off) + _f(3, dur))
        for m, off, dur in ((1, 0, 5000), (2, 6000, 2000), (1, 9000, 300)))
    steps = _f(2, b"Steps") + _f(4, _f(1, 2) + _f(3, 7))
    tpu = (_f(1, 3) + _f(2, b"/device:TPU:0") + _f(3, steps) + _f(3, ops)
           + event_meta + stat_meta)
    host = _f(1, 4) + _f(2, b"/host:CPU") + _f(3, _f(2, b"python"))
    return _f(1, host) + _f(1, tpu)


def test_name_stacks_read_from_the_protobuf(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    got = scopes.name_stacks(str(path), {"/device:TPU:0"})
    assert got == {"/device:TPU:0": [("jit(f)/fl.pack/abs", 5000),
                                     (None, 2000),
                                     ("jit(f)/fl.pack/abs", 300)]}
    assert scopes.name_stacks(str(path), {"/device:TPU:1"}) == {}


def test_recorded_chip_trace_by_scope_and_span():
    """One traced mlp-int8-xdevice call on one v5e chip, with the
    program's spans and scopes."""
    compact = json.loads((DATA / "trace_mlp_int8_scopes_v5e.json")
                         .read_text())
    rec = compact["expected"]
    r = scopes.reduce(compact)
    for key in ("scopes", "idle_spans"):
        assert dict(r[key]) == pytest.approx(dict(rec[key]), rel=1e-9)
    old = tracelib.reduce(compact)
    assert old["busy_s"] == pytest.approx(rec["busy_s"], rel=1e-9)
    assert old["window_s"] == pytest.approx(rec["window_s"], rel=1e-9)
    got = dict(r["scopes"])
    assert sum(got.values()) == pytest.approx(old["busy_s"], rel=1e-9)
    for scope in ("fl.gather", "fl.local_train", "fl.pack", "fl.aggregate"):
        assert got[scope] > 0
    idle = dict(r["idle_spans"])
    assert sum(idle.values()) + old["busy_s"] == pytest.approx(
        old["window_s"], rel=1e-9)
    named = sum(t for n, t in idle.items() if n.startswith("fl."))
    assert named > 0.9 * sum(idle.values())


def test_recorded_chip_trace_reduces_as_first_computed():
    """``bench.trace.reduce`` of its recorded trace reads every key as it
    did when the benchmark was first accepted."""
    compact = json.loads((DATA / "trace_mlp_int8_v5e.json").read_text())
    want = json.loads((DATA / "trace_mlp_int8_v5e.reduce.json")
                      .read_text())["reduce"]
    got = json.loads(json.dumps(tracelib.reduce(compact)))
    assert set(got) == set(want)
    for key in ("chunks", "op_calls", "kernels"):
        assert got[key] == pytest.approx(want[key], rel=1e-12)
    for key in ("window_s", "busy_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-12)
    for key in ("device_ops", "idle_gaps"):
        assert [n for n, _ in got[key]] == [n for n, _ in want[key]]
        assert [t for _, t in got[key]] == pytest.approx(
            [t for _, t in want[key]], rel=1e-12)


def _span(sid, parent, name, dur):
    return {"kind": "span", "id": sid, "parent": parent, "name": name,
            "dur_us": dur}


def _ctx(events, calls=2):
    return {"spans": events, "window_calls": calls, "trace": None}


def handmade_spans():
    """Set-up's call, two calls of the window, and a plan that the
    prefetch thread made (no parent)."""
    out, sid = [], 0
    for durs in ((900, 500, 80), (100, 60, 10), (140, 80, 30)):
        stage, plan, pull = durs
        chunk, launch = sid, sid + 1
        out += [_span(chunk, None, "chunk", 2000),
                _span(launch, chunk, "launch", 1500),
                _span(sid + 2, launch, "stage_slab", stage),
                _span(sid + 3, sid + 2, "cohort_plan", plan),
                _span(sid + 4, launch, "dispatch", 20),
                _span(sid + 5, launch, "device_wait", 500),
                _span(sid + 6, launch, "metrics_pull", pull),
                _span(sid + 7, chunk, "finish_chunk", 30)]
        sid += 8
    out.append(_span(sid, None, "cohort_plan", 5000))
    out.append({"kind": "counter", "name": "host", "values": {}})
    return out


def test_span_readers_per_call():
    ctx = _ctx(handmade_spans())
    assert cohort_plan_ms.read(ctx) == pytest.approx((60 + 80) / 2 / 1e3)
    assert slab_stage_ms.read(ctx) == pytest.approx((40 + 60) / 2 / 1e3)
    assert metrics_pull_ms.read(ctx) == pytest.approx((10 + 30) / 2 / 1e3)
    assert spans.per_call_ms(ctx, "device_wait") == pytest.approx(0.5)


def test_span_readers_find_nothing_without_their_span():
    events = [e for e in handmade_spans()
              if e.get("name") not in ("cohort_plan", "stage_slab",
                                       "metrics_pull")]
    for ctx in (_ctx(events), _ctx([]), _ctx(handmade_spans(), calls=0)):
        assert all(m.read(ctx) is None for m in
                   (cohort_plan_ms, slab_stage_ms, metrics_pull_ms))


def test_run_reduces_a_trace_by_op_scope_and_span():
    """``run.reduce_trace``: one dict for the readers, with the scopes,
    and the breakdown the result line carries."""
    from bench import run
    reduced, breakdown = run.reduce_trace(handmade())
    assert reduced["busy_s"] == pytest.approx(90e-9)
    assert dict(reduced["scopes"]) == dict(scopes.reduce(handmade())
                                           ["scopes"])
    assert set(breakdown) == {"device_ops", "idle_gaps", "scopes",
                              "idle_spans"}
    assert all(len(v) <= 10 for v in breakdown.values())
    c = handmade()
    c["devices"][0]["events"] = []
    assert run.reduce_trace(c) == (None, None)


@pytest.mark.parametrize("reader,ns", [(local_train_ms, 40),
                                       (pack_ms, 10), (aggregate_ms, 20)])
def test_scope_readers_per_call(reader, ns):
    from bench import run
    reduced, _ = run.reduce_trace(handmade())
    assert reader.read({"trace": reduced}) == pytest.approx(ns * 1e-6)
    reduced["chunks"] = 2
    assert reader.read({"trace": reduced}) == pytest.approx(ns * 1e-6 / 2)
    reduced["scopes"] = [s for s in reduced["scopes"] if s[0] == "other"]
    assert reader.read({"trace": reduced}) is None
    assert reader.read({"trace": None}) is None


def test_recorded_chip_trace_feeds_the_scope_readers():
    """Scope seconds of the recorded traced call add up to its busy time,
    and each scope reader reads its share of it."""
    from bench import run
    compact = json.loads((DATA / "trace_mlp_int8_scopes_v5e.json")
                         .read_text())
    reduced, breakdown = run.reduce_trace(compact)
    got = {m.__name__.rsplit(".", 1)[1]: m.read({"trace": reduced})
           for m in (local_train_ms, pack_ms, aggregate_ms)}
    assert all(v > 0 for v in got.values()), got
    assert sum(t for _, t in breakdown["scopes"]) == pytest.approx(
        reduced["busy_s"], rel=1e-9)
    assert got["pack_ms"] == pytest.approx(
        1e3 * dict(reduced["scopes"])["fl.pack"] / reduced["chunks"])
