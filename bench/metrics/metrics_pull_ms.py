"""Host time of the metrics pull per call, in ms: the recorder's
``metrics_pull`` spans, from the copy of the launch's metrics to the host
through the rows built from them (probe capture and comms accounting
included)."""
from bench import spans


def read(ctx):
    return spans.per_call_ms(ctx, "metrics_pull")
