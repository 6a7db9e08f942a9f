"""The flight recorder on the profiler's clock, and the device's scopes.

- With the recorder on, every span is also a ``fl.<name>`` TraceMe: a
  profiler capture of a ragged sync run and of an async run holds
  ``fl.launch`` and its children (``stage_slab``/``cohort_plan``,
  ``dispatch``, ``device_wait``, ``metrics_pull``) on one host thread,
  nested as the recorder's parents say. With the recorder off, no ``fl.``
  event appears.
- Spans opened on a stager's prefetch thread keep a stack of their own.
- The lowered programs of the sync ragged, async and campaign drivers carry
  the ``fl.*`` named scopes; scopes are metadata, so results stay bitwise.
- The telemetry report files the new spans under stage/execute, and every
  span of a cold launch under compile.
"""
import glob
import os
import re
import threading

os.environ.setdefault("REPRO_KERNEL_IMPL", "jnp")

import jax
import numpy as np
import pytest

from repro.core.jobs import load_job
from repro.runtime.campaign import CampaignExecutor
from repro.runtime.executor import Executor
from repro.telemetry.recorder import FlightRecorder
from repro.telemetry.trace import report

LAUNCH_CHILDREN = {"dispatch", "device_wait", "metrics_pull"}


def _raw(*, mode="sync", ragged=True, int8=False, telemetry=None,
         sweep=None, **extra):
    tp = {"n_clients": 8, "local_epochs": 1, "client_lr": 0.1, "rounds": 4,
          "seed": 5, "rounds_per_launch": 2, "batch_size": 4,
          "local_steps": 2}
    if ragged:
        tp.update(cohort=4, max_cohort=6)
    if mode == "async":
        tp.update(mode="async", async_buffer=3, max_staleness=4,
                  staleness_exponent=0.5)
    if int8:
        tp.update(compression="int8", error_feedback=False)
    tp.update(extra)
    raw = {"name": "trace-spans", "model": {"arch": "flsim-mlp"},
           "dataset": {"dataset": "synthetic_vision", "n_items": 96},
           "strategy": {"strategy": "compressed" if int8 else "fedavg",
                        "train_params": tp},
           "runtime": {"straggler_prob": 0.2,
                       "straggler_overprovision": 1.25}}
    if telemetry is not None:
        raw["telemetry"] = telemetry
    if sweep is not None:
        raw["sweep"] = sweep
    return raw


def _params(ex):
    return jax.tree.map(np.asarray, ex.state["params"])


def _host_events(profile_dir):
    """(line name, event name, start ns, duration ns) of every host event
    in the newest capture under ``profile_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    assert paths, f"no capture under {profile_dir}"
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(line.name, e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
    return out


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_profiler_capture_holds_the_spans_nested(tmp_path, mode):
    """``profile_chunks`` captures launch 1; the capture's ``fl.*`` events
    are the recorder's spans of that launch, one to one, in open order, on
    one thread, each inside its parent's event."""
    ex = Executor(load_job(_raw(mode=mode, telemetry={
        "out_dir": str(tmp_path), "profile_chunks": [1],
        "cost_analysis": False}))).scaffold()
    ex.run()
    fl = [e for e in _host_events(str(tmp_path / "jax_profile"))
          if e[1].startswith("fl.")]
    assert len({line for line, *_ in fl}) == 1, "fl.* spans on two threads"
    fl.sort(key=lambda e: (e[2], -e[3]))

    spans = [e for e in ex.recorder.events if e["kind"] == "span"]
    launch = next(e for e in spans if e["name"] == "launch"
                  and e["attrs"]["ordinal"] == 1)
    under = {launch["id"]}
    for e in sorted(spans, key=lambda e: e["id"]):
        if e["parent"] in under:
            under.add(e["id"])
    mine = sorted((e for e in spans if e["id"] in under),
                  key=lambda e: e["id"])
    assert [e[1] for e in fl] == ["fl." + e["name"] for e in mine]

    want = {"launch", "stage_slab"} | LAUNCH_CHILDREN
    if mode == "sync":
        want.add("cohort_plan")
    assert want <= {e["name"] for e in mine}
    interval = {s["id"]: (e[2], e[2] + e[3]) for s, e in zip(mine, fl)}
    parent_name = {s["id"]: s["name"] for s in mine}
    for s in mine:
        if s["id"] == launch["id"]:
            continue
        lo, hi = interval[s["parent"]]
        assert lo <= interval[s["id"]][0] <= interval[s["id"]][1] <= hi
        assert parent_name[s["parent"]] == {
            "cohort_plan": "stage_slab"}.get(s["name"], "launch")


def test_cohort_plan_batch_counts_each_chunk_once():
    """Each chunk's plan is one batched call: one ``cohort_plan_batch``
    counter per chunk, inside its ``cohort_plan`` span (itself under
    ``stage_slab``), with ``rounds`` the chunk's round count."""
    ex = Executor(load_job(_raw(rounds=5, telemetry={
        "cost_analysis": False}))).scaffold()
    ex.run()
    events = ex.recorder.events
    spans = {e["id"]: e for e in events if e["kind"] == "span"}
    chunks = [e["attrs"]["n"] for e in sorted(spans.values(),
                                              key=lambda e: e["id"])
              if e["name"] == "chunk"]
    plans = [e for e in events if e["kind"] == "counter"
             and e["name"] == "cohort_plan_batch"]
    assert chunks == [2, 2, 1]
    assert [e["values"]["rounds"] for e in plans] == chunks
    assert sum(e["values"]["compiled"] for e in plans) <= 2
    plan_spans = sorted((e for e in spans.values()
                         if e["name"] == "cohort_plan"),
                        key=lambda e: e["id"])
    assert len(plan_spans) == len(plans)
    for sp, c in zip(plan_spans, plans):
        assert spans[sp["parent"]]["name"] == "stage_slab"
        assert sp["t0_us"] <= c["t_us"] <= sp["t0_us"] + sp["dur_us"]


def test_recorder_off_puts_no_fl_events(tmp_path):
    ex = Executor(load_job(_raw())).scaffold()
    ex.run(rounds=2)
    with jax.profiler.trace(str(tmp_path)):
        ex.run(rounds=4)
    events = _host_events(str(tmp_path))
    assert events, "the capture holds no host events at all"
    assert not [e for e in events if e[1].startswith("fl.")]


def test_streaming_prefetch_keeps_the_stacks_apart():
    """The streaming stager plans the next chunk on its prefetch thread:
    those ``cohort_plan`` spans are roots on a track of their own, and
    the main thread's spans nest exactly as they were opened."""
    ex = Executor(load_job(_raw(streaming=True, rounds=6, telemetry={
        "cost_analysis": False}))).scaffold()
    ex.run()
    spans = {e["id"]: e for e in ex.recorder.events if e["kind"] == "span"}
    assert len(spans) == len([e for e in ex.recorder.events
                              if e["kind"] == "span"])
    off = [e for e in spans.values() if e["track"] != "run"]
    assert off and all(e["name"] == "cohort_plan" and e["parent"] is None
                       and e["depth"] == 0
                       and e["track"].startswith("run/slab-stager")
                       for e in off)
    for e in spans.values():
        if e["track"] == "run" and e["parent"] is not None:
            par = spans[e["parent"]]
            assert par["track"] == "run"
            assert e["depth"] == par["depth"] + 1
            assert par["t0_us"] <= e["t0_us"]
            assert e["t0_us"] + e["dur_us"] <= par["t0_us"] + par["dur_us"]
    assert ex.recorder._thread_stack() == []
    # the chunks' own plans ran on the main thread, under stage_slab
    assert any(e["name"] == "cohort_plan" and e["track"] == "run"
               and spans[e["parent"]]["name"] == "stage_slab"
               for e in spans.values())


def test_a_span_on_another_thread_never_pops_the_main_stack():
    rec = FlightRecorder()
    seen = {}

    def worker():
        with rec.span("cohort_plan"):
            seen["inner"] = list(rec._thread_stack())

    with rec.span("chunk"):
        with rec.span("launch"):
            t = threading.Thread(target=worker, name="prefetch")
            t.start()
            t.join()
            assert [s.name for s in rec._thread_stack()] == ["chunk",
                                                             "launch"]
    by_name = {e["name"]: e for e in rec.events}
    assert [s.name for s in seen["inner"]] == ["cohort_plan"]
    assert by_name["cohort_plan"]["parent"] is None
    assert by_name["cohort_plan"]["track"] == "run/prefetch"
    assert by_name["launch"]["parent"] == by_name["chunk"]["id"]
    assert rec._thread_stack() == []


def test_spans_from_many_threads_lose_nothing(tmp_path):
    """More threads than cores open nested spans while the main thread
    does too: every span is recorded and written once, with its parent on
    its own thread."""
    import sys
    from repro.telemetry.recorder import read_events
    rec = FlightRecorder(out_dir=tmp_path)
    n_threads, n_spans = 2 * (os.cpu_count() or 1) + 2, 200

    def worker():
        for _ in range(n_spans):
            with rec.span("stage_slab"):
                with rec.span("cohort_plan"):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, name=f"w{i}")
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        worker()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    rec.close()
    spans = {e["id"]: e for e in rec.events if e["kind"] == "span"}
    assert len(spans) == 2 * n_spans * (n_threads + 1)
    for e in spans.values():
        if e["name"] == "cohort_plan":
            assert spans[e["parent"]]["track"] == e["track"]
            assert spans[e["parent"]]["name"] == "stage_slab"
        else:
            assert e["parent"] is None
    assert {e["track"] for e in spans.values()} == {"run"} | {
        f"run/w{i}" for i in range(n_threads)}
    written = [e for e in read_events(tmp_path) if e["kind"] == "span"]
    assert sorted(e["id"] for e in written) == sorted(spans)


@pytest.mark.parametrize("driver", ["sync", "streaming", "async"])
def test_int8_ragged_bitwise_with_recorder(driver, tmp_path):
    """The new spans sit only on the host: on == off bitwise on the
    ragged int8 paths they instrument."""
    kw = {"mode": "async"} if driver == "async" else {}
    if driver == "streaming":
        kw["streaming"] = True
    off = Executor(load_job(_raw(int8=True, **kw))).scaffold()
    off.run()
    on = Executor(load_job(_raw(int8=True, telemetry={
        "out_dir": str(tmp_path)}, **kw))).scaffold()
    on.run()
    for a, b in zip(jax.tree.leaves(_params(off)),
                    jax.tree.leaves(_params(on))):
        np.testing.assert_array_equal(a, b)
    names = {e["name"] for e in on.recorder.events if e["kind"] == "span"}
    assert {"stage_slab"} | LAUNCH_CHILDREN <= names


def _lowered_text(driver, int8):
    if driver == "campaign":
        ex = CampaignExecutor(load_job(_raw(
            int8=int8, sweep={"client_lr": [0.05, 0.1]}))).scaffold()
        staged = ex.stager.slab(0, 2)
        low = ex._round_program(2).lower(ex.state, staged, ex.roots,
                                         ex._launch_hyper(), 0)
    elif driver == "async":
        ex = Executor(load_job(_raw(mode="async", int8=int8))).scaffold()
        n_ev = 2 * ex.events_per_round
        staged = ex.stager.event_slab(ex.schedule.client[:n_ev],
                                      tag=(0, n_ev))
        low = ex._event_program(n_ev).lower(ex.state, staged, ex.sched_dev,
                                            ex.root, ex.hyper, 0)
    else:
        ex = Executor(load_job(_raw(int8=int8))).scaffold()
        low = ex._round_program(2).lower(ex.state, ex.stager.slab(0, 2),
                                         ex.root, ex.hyper, 0)
    return low.as_text(debug_info=True)


def _scopes(text):
    """Every ``fl.*`` scope in the ops' locations (a transform wraps it,
    as in ``vmap(fl.local_train)``)."""
    return {scope for name in re.findall(r'loc\("([^"]*)"', text)
            for scope in re.findall(r"fl\.[a-z_]+", name)}


@pytest.mark.parametrize("driver,int8", [("sync", True), ("sync", False),
                                         ("async", True),
                                         ("campaign", True)])
def test_lowered_programs_carry_the_scopes(driver, int8):
    scopes = _scopes(_lowered_text(driver, int8))
    for scope in ("fl.gather", "fl.local_train", "fl.aggregate"):
        assert scope in scopes, f"{driver}: no {scope}"
    assert ("fl.pack" in scopes) == int8


def _span(sid, parent, name, dur, **attrs):
    return {"kind": "span", "id": sid, "parent": parent, "depth": 0,
            "name": name, "track": "run", "t0_us": 0, "dur_us": dur,
            "attrs": attrs}


def test_report_files_the_launch_children():
    """A cold launch takes every span under it to compile; under a warm
    launch the staging spans are stage and the rest execute."""
    events = [
        _span(0, None, "chunk", 1000000),
        _span(1, 0, "launch", 900000, compile_delta=1),
        _span(2, 1, "stage_slab", 100000),
        _span(3, 2, "cohort_plan", 60000),
        _span(4, 1, "dispatch", 700000),
        _span(5, 1, "device_wait", 50000),
        _span(6, 1, "metrics_pull", 40000),
        _span(7, None, "chunk", 500000),
        _span(8, 7, "launch", 400000, compile_delta=0),
        _span(9, 8, "stage_slab", 100000),
        _span(10, 9, "cohort_plan", 70000),
        _span(11, 8, "dispatch", 20000),
        _span(12, 8, "device_wait", 200000),
        _span(13, 8, "metrics_pull", 30000),
    ]
    rows = {line.split()[0]: line.split()[1:] for line in
            report(events).splitlines()[2:]
            if line.split() and line.split()[0] in ("compile", "execute",
                                                    "stage", "host")}
    # compile: the whole cold launch (0.9 s); execute: the warm launch's
    # self time (0.05 s) + dispatch, device_wait, metrics_pull (0.25 s);
    # stage: the warm stage_slab (0.1 s with its cohort_plan)
    assert float(rows["compile"][0]) == pytest.approx(0.9)
    assert int(rows["compile"][2]) == 6
    assert float(rows["execute"][0]) == pytest.approx(0.3)
    assert float(rows["stage"][0]) == pytest.approx(0.1)
    assert int(rows["stage"][2]) == 2
    assert float(rows["host"][0]) == pytest.approx(0.2)
