"""Host time of the cohort plan per call, in ms: the recorder's
``cohort_plan`` spans (``SlabStager.plan``, which replays the program's
cohort draw eagerly and pulls it to the host) under the window's calls.
A plan that a prefetch thread made ahead of its call is not counted."""
from bench import spans


def read(ctx):
    return spans.per_call_ms(ctx, "cohort_plan")
