"""The flight recorder's spans under the calls of a traced window.

A call of the window is one ``chunk`` span of the executor; the spans a
call opened are its descendants (``launch`` and its ``stage_slab``,
``cohort_plan``, ``dispatch``, ``device_wait``, ``metrics_pull``, then
``finish_chunk``). Spans that a stager's prefetch thread opened are roots
of that thread and belong to no call.
"""
from __future__ import annotations


def per_call_ms(ctx, name: str, minus: str | None = None):
    """Duration of the spans named ``name`` under the window's last
    ``window_calls`` chunks, less that of their children named ``minus``,
    in ms per call; None when no such span ran in them."""
    spans = sorted((e for e in ctx["spans"] if e.get("kind") == "span"),
                   key=lambda e: e["id"])
    chunks = [e for e in spans if e["name"] == "chunk"]
    chunks = chunks[-ctx["window_calls"]:] if ctx["window_calls"] else []
    under = {e["id"] for e in chunks}
    mine = []
    for e in spans:          # a parent's id is below its children's
        if e["parent"] in under:
            under.add(e["id"])
            if e["name"] == name:
                mine.append(e)
    if not mine:
        return None
    ids = {e["id"] for e in mine}
    less = sum(e["dur_us"] for e in spans
               if e["name"] == minus and e["parent"] in ids)
    return (sum(e["dur_us"] for e in mine) - less) / len(chunks) / 1e3
