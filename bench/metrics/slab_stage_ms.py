"""Host time of slab staging per call, in ms: the self time of the
recorder's ``stage_slab`` spans (the slab's gather or host assembly and
copy, the prefetch wait and kick) less their ``cohort_plan`` children."""
from bench import spans


def read(ctx):
    return spans.per_call_ms(ctx, "stage_slab", minus="cohort_plan")
