"""Device time of the local step per call, in ms: the self time of the ops
in the program's ``fl.local_train`` scope (the clients' SGD steps:
forward, backward, update) in the traced window (bench/scopes.py), over
the calls the trace holds."""
from bench import scopes


def read(ctx):
    return scopes.per_call_ms(ctx, "fl.local_train")
