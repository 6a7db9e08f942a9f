"""The whole step's share of the chip's bf16 peak, in %: the training
FLOPs of the images the traced calls trained (bench/costs.py), over the
traced window, over the peak."""
from bench import costs


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    shape = ctx["shape"]
    images = tr["chunks"] * shape["updates_per_call"] \
        * shape["images_per_update"]
    flops = images * costs.train_flops_per_image(
        ctx["cell"]["config"]["published"])
    return 100.0 * flops / tr["window_s"] / ctx["peaks"]["bf16_flops_per_s"]
