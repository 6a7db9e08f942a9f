"""Reduction of a profiler trace to device busy time, op times and gaps.

``from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps what the metrics need, as plain JSON-able data: per device plane the
events of its "XLA Ops" line, and the host events of the thread that ran
the window (the harness wraps each call of the window in a ``bench_chunk``
annotation). ``reduce`` turns that into the traced window, the device's
busy time in it, the self time of each op (a ``while`` op's events enclose
its body's), the calls and time of each HLO instruction by its base name
(``%quant_aggregate.9 = ...`` counts as ``quant_aggregate``), and the idle
gaps labelled by the innermost host event open at the gap's middle.

A Pallas kernel is a ``custom-call``. XLA may stage its operands into VMEM
(memory space ``S(1)``) with ops of their own just before the call, which
then do the kernel's reads from HBM; ``kernels`` charges each call with
the time of the ops that produced its ``S(1)`` operands.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

CHUNK = "bench_chunk"
OP_LINES = ("XLA Ops",)
_BASE = re.compile(r"^%?([A-Za-z_][A-Za-z0-9_\-]*?)(\.\d+)*( = |$)")


def base_name(op: str) -> str:
    """``%quant_aggregate.9 = f32[...] custom-call(...)`` -> quant_aggregate."""
    m = _BASE.match(op)
    return m.group(1) if m else op.split(" ")[0]


def _result_type(op: str) -> str:
    return op.split(" = ")[1].split(" ")[0] if " = " in op else ""


def _operands(op: str) -> list:
    args = op.split("custom-call(", 1)[1].split("custom_call_target", 1)[0]
    return re.findall(r"%[\w.\-]+", args)


def label(op: str) -> str:
    """An op's name as the breakdown shows it: the instruction and its
    result shape, without the operands."""
    return op.split(" = ")[0] + (" = " + op.split(" = ")[1].split(" ")[0]
                                 if " = " in op else "")


def from_xplane(profile_dir: str, n_devices: int) -> dict:
    """Compact form of the newest trace under ``profile_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            for line in plane.lines:
                if line.name in OP_LINES:
                    devices.append({"plane": plane.name, "events": [
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events]})
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                events = [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events]
                if any(e[0] == CHUNK for e in events):
                    host.extend(events)
    devices.sort(key=lambda d: d["plane"])
    return {"devices": devices[:n_devices], "host": host}


def _innermost(host):
    """Host timeline as (change times, label of the innermost open event
    from that time on); events of one thread nest."""
    starts, labels, stack = [], [], []

    def close_until(t):
        while stack and stack[-1][1] <= t:
            end = stack.pop()[1]
            starts.append(end)
            labels.append(stack[-1][2] if stack else None)

    for name, s, d in sorted(host, key=lambda h: (h[1], -h[2])):
        close_until(s)
        stack.append((s, s + d, name))
        starts.append(s)
        labels.append(name)
    close_until(float("inf"))
    return starts, labels


def _close(stack, ops):
    end, name, child, dur = stack.pop()
    key = label(name)
    ops[key] = ops.get(key, 0.0) + (dur - child) * 1e-9


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(compact: dict, top: int = 10) -> dict | None:
    """Window, busy seconds (mean over devices), op self seconds, calls
    per instruction, kernel calls with their staging, idle gaps.

    None when the trace holds no chunk or no device op: nothing to read."""
    chunks = [(s, s + d) for n, s, d in compact["host"] if n == CHUNK]
    devices = [d for d in compact["devices"] if d["events"]]
    if not chunks or not devices:
        return None
    w0, w1 = min(s for s, _ in chunks), max(e for _, e in chunks)
    busy, ops, gaps, calls, kernels = [], {}, [], {}, {}
    for dev in devices:
        spans = []
        staged = {}  # instruction -> duration of its latest VMEM result
        stack = []   # [end, name, child time, duration] of open ops
        for name, s, d in sorted(dev["events"], key=lambda e: (e[1], -e[2])):
            s, e = max(s, w0), min(s + d, w1)
            if e <= s:
                continue
            while stack and stack[-1][0] <= s:
                _close(stack, ops)
            if stack:
                stack[-1][2] += e - s
            stack.append([e, name, 0.0, e - s])
            spans.append((s, e))
            k = calls.setdefault(base_name(name), [0, 0.0])
            k[0] += 1
            k[1] += (e - s) * 1e-9
            if "S(1)" in _result_type(name):
                staged[name.split(" = ")[0]] = e - s
            if "custom-call(" in name:
                k = kernels.setdefault(base_name(name), [0, 0.0])
                k[0] += 1
                k[1] += (e - s + sum(staged.get(o, 0.0)
                                     for o in _operands(name))) * 1e-9
        while stack:
            _close(stack, ops)
        merged = _union(spans)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    starts, labels = _innermost(compact["host"])
    idle = {}
    for s, e in gaps:
        i = bisect.bisect_right(starts, (s + e) / 2) - 1
        what = (labels[i] if i >= 0 else None) or "outside the calls"
        idle[what] = idle.get(what, 0.0) + (e - s) * 1e-9 / len(devices)
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": sum(busy) / len(busy),
            "chunks": len(chunks), "op_calls": calls, "kernels": kernels,
            "device_ops": sorted(([n, t] for n, t in ops.items()),
                                 key=lambda x: -x[1])[:top],
            "idle_gaps": sorted(([n, t] for n, t in idle.items()),
                                key=lambda x: -x[1])[:top]}
