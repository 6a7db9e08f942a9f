"""Device time of aggregation per call, in ms: the self time of the ops in
the program's ``fl.aggregate`` scope (the weighted sum of the clients'
deltas and the server update; the int8 kernel where the cell compresses)
in the traced window (bench/scopes.py), over the calls the trace holds."""
from bench import scopes


def read(ctx):
    return scopes.per_call_ms(ctx, "fl.aggregate")
