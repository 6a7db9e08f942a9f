"""Device time by the program's ``fl.*`` scopes, idle time by its spans.

The program names its phases on both sides of the chip. Its flight
recorder's spans appear in a profile as ``fl.<span>`` TraceMe events on the
host thread that ran them (``repro/telemetry/recorder.py``), and its device
ops carry ``jax.named_scope``s (``fl.gather``, ``fl.local_train``,
``fl.pack``, ``fl.aggregate``) in their name stack. On a TPU the name stack
is the ``tf_op`` stat of each op's event metadata, as in
``jit(<lambda>)/while/body/closed_call/vmap(fl.local_train)/...``.
``jax.profiler.ProfileData`` does not show metadata stats, so
``name_stacks`` reads them from the ``.xplane.pb`` itself: a few fields of
its protobuf wire format, with no library beyond Python's.

``from_xplane`` is ``bench.trace.from_xplane`` plus, for each device, a
``scopes`` list beside its ``events``: the innermost ``fl.*`` scope of each
op, or None. ``reduce`` turns that into

- ``scopes``: device self seconds by scope (``other`` for ops outside every
  scope) in the traced window, the mean over devices;
- ``idle_spans``: the device's idle gaps, labelled by the innermost host
  event named ``fl.*`` open at the gap's middle, or ``outside the spans``.

Window, self time and gaps are those of ``bench.trace.reduce``, so the
scopes add up to its ``busy_s`` and ``idle_spans`` to its idle time.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

from bench import trace as tracelib

NAME_STACK = "tf_op"
OTHER = "other"
OUTSIDE = "outside the spans"
_SCOPE = re.compile(r"fl\.[A-Za-z_]+")


def scope_of(stack) -> str | None:
    """The innermost ``fl.*`` component of a name stack (a transform may
    wrap it: ``vmap(fl.local_train)``), or None."""
    found = _SCOPE.findall(stack or "")
    return found[-1] if found else None


# -- the .xplane.pb protobuf, as far as the name stacks need it -------------
# XSpace.planes = 1; XPlane: name = 2, lines = 3, event_metadata = 4 (map
# id -> XEventMetadata), stat_metadata = 5 (map id -> XStatMetadata);
# XLine: name = 2, events = 4; XEvent: metadata_id = 1, duration_ps = 3;
# XEventMetadata: stats = 5; XStatMetadata: name = 2; XStat: metadata_id =
# 1, str_value = 5, ref_value = 7 (the id of a stat metadata whose name is
# the string).

def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int, or a memoryview of a
    length-delimited field."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _entry(value):
    """(key, value) of a protobuf map entry."""
    kv = dict(_fields(value))
    return kv.get(1, 0), kv.get(2, b"")


def name_stacks(path, planes) -> dict:
    """{plane name: [(name stack or None, duration ps) per event of its
    op line, in the file's order]} for the planes named in ``planes``."""
    buf = memoryview(open(path, "rb").read())
    out = {}
    for field, plane in _fields(buf):
        if field != 1:
            continue
        name, lines, meta, stat_names = None, [], {}, {}
        for f, v in _fields(plane):
            if f == 2:
                name = _text(v)
            elif f == 3:
                lines.append(v)
            elif f == 4:
                k, md = _entry(v)
                meta[k] = md
            elif f == 5:
                k, sm = _entry(v)
                stat_names[k] = _text(dict(_fields(sm)).get(2, b""))
        if name not in planes:
            continue
        want = {k for k, n in stat_names.items() if n == NAME_STACK}
        stacks = {}
        for k, md in meta.items():
            for f, st in _fields(md):
                if f != 5:
                    continue
                s = dict(_fields(st))
                if s.get(1) in want:
                    stacks[k] = (_text(s[5]) if 5 in s
                                 else stat_names.get(s.get(7)))
        events = []
        for ln in lines:
            fields = list(_fields(ln))
            if any(f == 2 and _text(v) in tracelib.OP_LINES
                   for f, v in fields):
                for e in (dict(_fields(v)) for f, v in fields if f == 4):
                    events.append((stacks.get(e.get(1, 0)), e.get(3, 0)))
        out[name] = events
    return out


def from_xplane(profile_dir: str, n_devices: int) -> dict:
    """``bench.trace.from_xplane`` of the newest trace, each device with
    the innermost ``fl.*`` scope of every op beside its events."""
    compact = tracelib.from_xplane(profile_dir, n_devices)
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    stacks = name_stacks(paths[-1], {d["plane"] for d in compact["devices"]})
    for dev in compact["devices"]:
        mine = stacks.get(dev["plane"], [])
        if len(mine) != len(dev["events"]) or any(
                abs(ps / 1e3 - e[2]) > 1 for (_, ps), e
                in zip(mine, dev["events"])):
            raise ValueError(f"{dev['plane']}: the name stacks do not line "
                             "up with the profiler's events")
        dev["scopes"] = [scope_of(s) for s, _ in mine]
    return compact


def reduce(compact: dict, top: int = 10) -> dict | None:
    """``scopes`` and ``idle_spans`` of the traced window; None when it
    holds no chunk or no device op."""
    chunks = [(s, s + d) for n, s, d in compact["host"]
              if n == tracelib.CHUNK]
    devices = [d for d in compact["devices"] if d["events"]]
    if not chunks or not devices:
        return None
    w0, w1 = min(s for s, _ in chunks), max(e for _, e in chunks)
    scopes, gaps = {}, []
    for dev in devices:
        spans, stack = [], []      # stack: [end, scope, child time, time]
        rows = sorted(zip(dev["events"], dev.get("scopes")
                          or [None] * len(dev["events"])),
                      key=lambda r: (r[0][1], -r[0][2]))
        for (_, s, d), scope in rows:
            s, e = max(s, w0), min(s + d, w1)
            if e <= s:
                continue
            while stack and stack[-1][0] <= s:
                _close(stack, scopes)
            if stack:
                stack[-1][2] += e - s
            stack.append([e, scope or OTHER, 0.0, e - s])
            spans.append((s, e))
        while stack:
            _close(stack, scopes)
        merged = tracelib._union(spans)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    starts, labels = tracelib._innermost(
        [h for h in compact["host"] if h[0].startswith("fl.")])
    idle = {}
    for s, e in gaps:
        i = bisect.bisect_right(starts, (s + e) / 2) - 1
        what = (labels[i] if i >= 0 else None) or OUTSIDE
        idle[what] = idle.get(what, 0.0) + (e - s) * 1e-9 / len(devices)
    return {"scopes": sorted(([n, t * 1e-9 / len(devices)]
                              for n, t in scopes.items()),
                             key=lambda x: -x[1]),
            "idle_spans": sorted(([n, t] for n, t in idle.items()),
                                 key=lambda x: -x[1])[:top]}


def _close(stack, scopes):
    _, scope, child, dur = stack.pop()
    scopes[scope] = scopes.get(scope, 0.0) + dur - child


def per_call_ms(ctx, scope: str):
    """Device self time of ``scope`` per call of the traced window, in ms;
    None when the trace holds no op of that scope."""
    tr = ctx["trace"]
    if tr is None or not tr.get("chunks"):
        return None
    seconds = dict(tr.get("scopes", [])).get(scope)
    return None if seconds is None else 1e3 * seconds / tr["chunks"]
