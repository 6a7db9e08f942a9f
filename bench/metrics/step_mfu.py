"""The whole step's share of the chip's bf16 peak, in %: the training
FLOPs of the items the traced calls trained (the family's
``train_flops_per_item``), over the traced window, over the peak."""
from bench import families


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    shape, conf = ctx["shape"], ctx["cell"]["config"]
    items = tr["chunks"] * shape["updates_per_call"] \
        * shape["items_per_update"]
    flops = items * families.of(conf).train_flops_per_item(conf)
    return 100.0 * flops / tr["window_s"] / ctx["peaks"]["bf16_flops_per_s"]
