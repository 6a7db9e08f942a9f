"""The paper's image classifiers (``flsim-cnn``, ``flsim-mlp``) on
synthetic CIFAR-shaped images: data, model, local training and costs.

It imports nothing of the program. From the seed it derives again what the
program derives: the images (class prototypes plus Gaussian noise), the
client partition and the initial weights. ``conf["published"]`` holds the
widths: ``conv_channels``, ``conv_kernel`` and ``fc_width`` for the CNN,
``hidden_layers`` and ``hidden_width`` for the MLP, and ``n_classes`` and
``input_shape`` for both.

The FLOP counts take the multiply-adds of convolutions and dense layers (2
FLOPs each): the forward pass, the weight gradients (as many again) and the
input gradients of every layer but the first (nothing needs the gradient of
the images). Biases, activations and pooling are not counted.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# The CPU tests run every vision cell on the paper's MLP: a CNN's
# convolutions take minutes there.
TINY_PUBLISHED = {"hidden_layers": 4, "hidden_width": 256, "n_classes": 10,
                  "input_shape": [32, 32, 3]}


# -- data ----------------------------------------------------------------

def synthetic_vision(n_items: int, seed: int, shape=(32, 32, 3),
                     n_classes: int = 10, noise: float = 0.8):
    """Class prototypes plus Gaussian noise, drawn in the program's order."""
    rng = np.random.RandomState(seed)
    y = rng.randint(0, n_classes, n_items)
    protos = rng.randn(n_classes, *shape).astype(np.float32)
    x = protos[y] + noise * rng.randn(n_items, *shape).astype(np.float32)
    return x, y


def partition(kind: str, labels, n_clients: int, seed: int) -> list:
    """Per-client sorted item indices (iid, or 2 label shards)."""
    rng = np.random.RandomState(seed)
    if kind == "iid":
        perm = rng.permutation(len(labels))
        return [np.sort(p) for p in np.array_split(perm, n_clients)]
    if kind == "shards":
        order = np.argsort(labels, kind="stable")
        shards = np.array_split(order, 2 * n_clients)
        assign = rng.permutation(len(shards))
        return [np.sort(np.concatenate([shards[assign[2 * i]],
                                        shards[assign[2 * i + 1]]]))
                for i in range(n_clients)]
    raise KeyError(kind)


def data(conf: dict, seed: int):
    published = conf["published"]
    x, y = synthetic_vision(conf["n_items"], seed,
                            tuple(published["input_shape"]),
                            published["n_classes"])
    return {"x": x, "y": y}, partition(conf["partition"], y,
                                       conf["n_clients"], seed)


# -- model -------------------------------------------------------------------

def init_params(conf: dict, seed: int) -> dict:
    """Seeded weights N(0, 1/fan_in), zero biases, in the program's key order."""
    published = conf["published"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 12)

    def dense(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * np.float32(
            1.0 / np.sqrt(fan_in))

    h, w, c = published["input_shape"]
    classes = published["n_classes"]
    if "conv_channels" in published:
        k = published["conv_kernel"]
        p = {}
        for i, ch in enumerate(published["conv_channels"]):
            p[f"c{i + 1}"] = dense(ks[i], (k, k, c, ch), k * k * c)
            p[f"b{i + 1}"] = jnp.zeros((ch,), jnp.float32)
            h, w, c = h // 2, w // 2, ch
        fc, d = published["fc_width"], h * w * c
        n = len(published["conv_channels"])
        p["fc"] = dense(ks[n], (d, fc), d)
        p["fb"] = jnp.zeros((fc,), jnp.float32)
        p["out"] = dense(ks[n + 1], (fc, classes), fc)
        p["ob"] = jnp.zeros((classes,), jnp.float32)
        return p
    d, width = h * w * c, published["hidden_width"]
    p = {}
    for i in range(published["hidden_layers"]):
        p[f"w{i}"] = dense(ks[i], (d, width), d)
        p[f"b{i}"] = jnp.zeros((width,), jnp.float32)
        d = width
    p["out"] = dense(ks[10], (d, classes), d)
    p["ob"] = jnp.zeros((classes,), jnp.float32)
    return p


def logits(conf: dict, p: dict, x):
    published = conf["published"]
    if "conv_channels" in published:
        h = x
        for i in range(len(published["conv_channels"])):
            h = jax.lax.conv_general_dilated(
                h, p[f"c{i + 1}"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC")) + p[f"b{i + 1}"]
            h = jax.nn.relu(h)
            h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        h = jax.nn.relu(h.reshape(h.shape[0], -1) @ p["fc"] + p["fb"])
        return h @ p["out"] + p["ob"]
    h = x.reshape(x.shape[0], -1)
    for i in range(published["hidden_layers"]):
        h = jax.nn.relu(h @ p[f"w{i}"] + p[f"b{i}"])
    return h @ p["out"] + p["ob"]


def loss(conf: dict, p: dict, batch: dict):
    """Mean cross-entropy of one batch ``{"x": images, "y": labels}``."""
    lp = jax.nn.log_softmax(logits(conf, p, batch["x"]).astype(jnp.float32))
    return -jnp.take_along_axis(lp, batch["y"][:, None], 1).mean()


def make_local_train(conf: dict, total_steps: int, n_steps: int,
                     half_batch: bool):
    """Jitted: ``total_steps`` plain SGD steps cycling over ``n_steps``
    batches; returns (new params, mean step loss)."""
    def train(p, batch, lr):
        if half_batch:
            batch = jax.tree.map(lambda a: a[:, :a.shape[1] // 2], batch)

        def step(p, i):
            value, g = jax.value_and_grad(lambda q, b: loss(conf, q, b))(
                p, jax.tree.map(lambda a: a[i % n_steps], batch))
            return jax.tree.map(lambda a, b: a - lr * b, p, g), value

        p, losses = jax.lax.scan(step, p, jnp.arange(total_steps))
        return p, losses.mean()

    return jax.jit(train)


# -- costs -------------------------------------------------------------------

def layer_flops(conf: dict) -> list:
    """Forward FLOPs per image of each weighted layer, input side first."""
    published = conf["published"]
    h, w, c = published["input_shape"]
    classes = published["n_classes"]
    out = []
    if "conv_channels" in published:
        k = published["conv_kernel"]
        for ch in published["conv_channels"]:
            out.append(2 * h * w * ch * k * k * c)   # SAME conv, stride 1
            h, w, c = h // 2, w // 2, ch             # 2x2 max pool
        d = h * w * c
        fc = published["fc_width"]
        out += [2 * d * fc, 2 * fc * classes]
    else:
        d = h * w * c
        width = published["hidden_width"]
        for _ in range(published["hidden_layers"]):
            out.append(2 * d * width)
            d = width
        out.append(2 * d * classes)
    return out


def forward_flops_per_item(conf: dict) -> int:
    return sum(layer_flops(conf))


def train_flops_per_item(conf: dict) -> int:
    """Forward + weight gradients + input gradients past the first layer."""
    per = layer_flops(conf)
    return 3 * sum(per) - per[0]


def leaf_sizes(conf: dict) -> list:
    """Sizes of the weight and bias leaves, layer by layer."""
    published = conf["published"]
    h, w, c = published["input_shape"]
    classes = published["n_classes"]
    out = []
    if "conv_channels" in published:
        k = published["conv_kernel"]
        for ch in published["conv_channels"]:
            out += [k * k * c * ch, ch]
            h, w, c = h // 2, w // 2, ch
        fc = published["fc_width"]
        return out + [h * w * c * fc, fc, fc * classes, classes]
    d, width = h * w * c, published["hidden_width"]
    for _ in range(published["hidden_layers"]):
        out += [d * width, width]
        d = width
    return out + [d * classes, classes]


def n_params(conf: dict) -> int:
    return sum(leaf_sizes(conf))


def tiny(conf: dict) -> dict:
    """The CPU tests' cut of any vision configuration: the paper's MLP at
    its published widths over 240 images in 12 clients of 2 label shards."""
    return dict(conf, model="flsim-mlp", published=dict(TINY_PUBLISHED),
                n_items=240, n_clients=12, partition="shards")
