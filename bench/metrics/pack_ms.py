"""Device time of packing per call, in ms: the self time of the ops in the
program's ``fl.pack`` scope (the clients' deltas quantized to int8 blocks
and laid out for the kernel) in the traced window (bench/scopes.py), over
the calls the trace holds."""
from bench import scopes


def read(ctx):
    return scopes.per_call_ms(ctx, "fl.pack")
