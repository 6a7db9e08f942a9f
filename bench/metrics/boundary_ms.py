"""Mean self time of the executor's ``finish_chunk`` span over the window's
calls, in ms: the host work at each chunk boundary, minus the child spans
(ledger, eval, probe/comms flushes, table flush) it opens."""


def read(ctx):
    spans = [e for e in ctx["spans"] if e.get("kind") == "span"]
    finish = [e for e in spans if e["name"] == "finish_chunk"]
    finish = finish[-ctx["window_calls"]:]
    if not finish:
        return None
    ids = {e["id"] for e in finish}
    child = {}
    for e in spans:
        if e.get("parent") in ids:
            child[e["parent"]] = child.get(e["parent"], 0) + e["dur_us"]
    selfs = [e["dur_us"] - child.get(e["id"], 0) for e in finish]
    return sum(selfs) / len(selfs) / 1e3
