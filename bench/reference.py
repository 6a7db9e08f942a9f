"""Plain reference of the benchmark's FL algorithms: one client at a time.

It imports nothing of the program. From the seed it derives again what the
program derives: the synthetic images, the client partition, the initial
weights, each round's cohort, each client's batches and, for async cells,
the virtual-clock event schedule. Then it trains each client in turn with
plain SGD in float32 at the highest matrix-product precision, quantizes its
delta to int8 blocks where the cell compresses, and applies the weighted
mean (sync) or the staleness-weighted buffer flush (FedBuff) on the server.

``run`` returns, per lane, the loss of every round and the parameters
before step 1, after step 1 and after the last step, where a step is one
call of the window (``rounds_per_launch`` rounds). ``dtype=jnp.bfloat16``
computes the same in bfloat16 (the control), and ``half_batch`` trains on
the first half of each batch (a planted fault).
"""
from __future__ import annotations

import collections
import heapq

import jax
import jax.numpy as jnp
import numpy as np

QBLOCK = 256
_F32 = np.float32


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


# -- keys ------------------------------------------------------------------

def _fold(key, *data):
    for d in data:
        key = jax.random.fold_in(key, d)
    return key


def cohort(seed: int, rnd: int, n_clients: int, target: int) -> np.ndarray:
    """Round ``rnd``'s cohort: the first ``target`` of a seeded permutation
    (no drops, no over-provisioning), in ascending client order."""
    pool, _ = jax.random.split(_fold(jax.random.PRNGKey(0xC047), seed, rnd))
    perm = np.asarray(jax.random.permutation(pool, n_clients))
    return np.sort(perm[:target])


# -- model -------------------------------------------------------------------

def init_params(published: dict, seed: int) -> dict:
    """Seeded weights N(0, 1/fan_in), zero biases, in the program's key order."""
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


def logits(published: dict, p: dict, x):
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


def make_local_train(published: dict, total_steps: int, n_steps: int,
                     half_batch: bool):
    """Jitted: ``total_steps`` SGD steps cycling over ``n_steps`` batches;
    returns (new params, mean step loss)."""
    def loss_fn(p, x, y):
        lp = jax.nn.log_softmax(logits(published, p, x).astype(jnp.float32))
        return -jnp.take_along_axis(lp, y[:, None], 1).mean()

    def train(p, xs, ys, lr):
        if half_batch:
            half = xs.shape[1] // 2
            xs, ys = xs[:, :half], ys[:, :half]

        def step(p, i):
            loss, g = jax.value_and_grad(loss_fn)(p, xs[i % n_steps],
                                                  ys[i % n_steps])
            return jax.tree.map(lambda a, b: a - lr * b, p, g), loss

        p, losses = jax.lax.scan(step, p, jnp.arange(total_steps))
        return p, losses.mean()

    return jax.jit(train)


@jax.jit
def roundtrip_int8(delta: dict) -> dict:
    """Each leaf in blocks of 256 (zero-padded): q = round(x / (max|x|/127))
    clipped to +-127, sent as q and the block's float32 scale, and
    dequantized as q * scale."""
    out = {}
    for name, d in delta.items():
        flat = d.reshape(-1).astype(jnp.float32)
        pad = (-flat.shape[0]) % QBLOCK
        blocks = jnp.pad(flat, (0, pad)).reshape(-1, QBLOCK)
        amax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(blocks / scale), -127, 127)
        deq = (q * scale).reshape(-1)[:flat.shape[0]]
        out[name] = deq.reshape(d.shape).astype(d.dtype)
    return out


# -- async event schedule ----------------------------------------------------

_TAG_RATE, _TAG_JITTER, _TAG_STRAGGLER, _TAG_AVAIL = 1, 2, 3, 4


def _draw(seed: int, tag: int, task: int):
    key = np.array([np.uint64(seed & 0xFFFFFFFF),
                    np.uint64((tag << 32) | (task & 0xFFFFFFFF))],
                   dtype=np.uint64)
    return np.random.Generator(np.random.Philox(key=key))


def schedule(seed: int, n_clients: int, n_events: int, weights, fl: dict,
             rt: dict) -> dict:
    """The virtual clock: clients train continuously, each task lasting
    ``mean_duration`` x lognormal(``duration_sigma``) x the straggler
    slowdown; arrivals at one time are taken in client order before anyone
    re-dispatches. An arrival of staleness s <= ``max_staleness`` joins the
    buffer with weight (1+s)^-exponent x its data size; every ``buffer``
    accepted arrivals the server applies their normalized sum."""
    C, E = int(n_clients), int(n_events)
    K = max(int(fl.get("async_buffer", 0)), 1)
    M = C if fl.get("async_concurrency", 0) <= 0 \
        else min(int(fl["async_concurrency"]), C)
    max_s = int(fl.get("max_staleness", 8))
    expo = float(fl.get("staleness_exponent", 0.0))
    mean_d = float(rt.get("mean_duration", 1.0))
    sigma = float(rt.get("duration_sigma", 0.25))
    p_strag = float(rt.get("straggler_prob", 0.0))
    slow = float(rt.get("straggler_slowdown", 4.0))
    p_ok = float(rt.get("availability", 1.0)) * (1.0 - float(
        rt.get("drop_prob", 0.0)))
    rate = np.exp(float(rt.get("rate_spread", 0.0)) * _draw(
        seed, _TAG_RATE, 0).standard_normal(C)).astype(_F32)
    cols_d, cols_ok = [], []

    def dur(c, t):
        while len(cols_d) <= t:
            j = len(cols_d)
            d = mean_d * rate
            if sigma != 0.0:
                d = d * np.exp(sigma * _draw(seed, _TAG_JITTER,
                                             j).standard_normal(C))
            if p_strag > 0.0:
                u = _draw(seed, _TAG_STRAGGLER, j).random(C)
                d = np.where(u < p_strag, d * slow, d)
            cols_d.append(np.asarray(d).astype(_F32))
        return float(cols_d[t][c])

    def usable(c, t):
        while len(cols_ok) <= t:
            cols_ok.append(_draw(seed, _TAG_AVAIL, len(cols_ok)).random(C)
                           < p_ok)
        return bool(cols_ok[t][c])

    w = np.asarray(weights, _F32)
    ev = {k: np.zeros(E, np.int64) for k in
          ("client", "task", "staleness", "start")}
    accept = np.zeros(E, bool)
    apply = np.zeros(E, bool)
    aw = np.zeros(E, _F32)
    den = np.ones(E, _F32)
    alpha_e = np.zeros(E, _F32)
    heap = [(dur(c, 0), c) for c in range(M)]
    heapq.heapify(heap)
    waiting = collections.deque(range(M, C))
    start = np.zeros(C, np.int64)
    done = np.zeros(C, np.int64)
    version, group, buf_den, e = 0, [], _F32(0.0), 0
    while e < E:
        t = heap[0][0]
        arrivals = []
        while heap and heap[0][0] == t:
            arrivals.append(heapq.heappop(heap)[1])
        for c in arrivals:
            if e >= E:
                break
            k = int(done[c])
            s = version - int(start[c])
            ok = usable(c, k) and s <= max_s
            alpha = _F32((1.0 + s) ** (-expo)) if ok else _F32(0.0)
            ev["client"][e], ev["task"][e] = c, k
            ev["staleness"][e], ev["start"][e] = s, start[c]
            accept[e] = ok
            aw[e] = alpha * w[c]
            alpha_e[e] = alpha
            if ok:
                group.append(e)
                buf_den = _F32(buf_den + aw[e])
                if len(group) >= K:
                    apply[e] = True
                    version += 1
                    den[group] = max(buf_den, _F32(1e-12))
                    group, buf_den = [], _F32(0.0)
            done[c] = k + 1
            e += 1
        waiting.extend(arrivals)
        while len(heap) < M and waiting:
            c = waiting.popleft()
            start[c] = version
            heapq.heappush(heap, (t + dur(c, int(done[c])), c))
    if group:
        den[group] = max(buf_den, _F32(1e-12))
    coeff = (aw / den).astype(_F32) if K > 1 else alpha_e
    return dict(ev, accept=accept, apply=apply, coeff=coeff,
                ring=max_s + 1)


# -- the algorithms ------------------------------------------------------------

def _host(p) -> dict:
    return {k: np.asarray(v, np.float32) for k, v in p.items()}


def make_client_update(published: dict, total_steps: int, n_steps: int,
                       batch: int, int8: bool, half_batch: bool):
    """Jitted: one client's round, from its seed-drawn batch positions to
    its (quantized) delta added into the server's weighted sum:
    (acc, start, x, y, parts, sizes, seed, rnd, client, lr, w) ->
    (acc, loss)."""
    train = make_local_train(published, total_steps, n_steps, half_batch)

    def client(acc, start, x, y, parts, sizes, seed, rnd, c, lr, w):
        key = _fold(jax.random.PRNGKey(seed), rnd, 0xBA7C, c)
        pos = jax.random.randint(key, (n_steps, batch), 0,
                                 jnp.maximum(sizes[c], 1))
        idx = parts[c][pos]
        new, loss = train(start, x[idx], y[idx], lr)
        delta = jax.tree.map(lambda a, b: a - b, new, start)
        if int8:
            delta = roundtrip_int8(delta)
        return jax.tree.map(lambda a, d: a + w * d, acc, delta), loss

    return jax.jit(client)


def run(cell: dict, seed: int, steps: int = 3, dtype=jnp.float32,
        half_batch: bool = False, precision: str | None = None) -> dict:
    """The cell's first ``steps`` calls, recomputed plainly.

    ``precision`` overrides the matrix-product precision (``highest`` in
    float32, ``default`` in bfloat16): float32 at ``default`` is what the
    configurations state the program computes, a witness in calibration.

    Returns {"losses": (lanes, rounds) array, "params": per lane
    [before step 1, after step 1, after step ``steps``]}."""
    from bench.cells import job_seed, lanes, train_params

    conf = cell["config"]
    fl = train_params(cell)
    seed = job_seed(seed)
    published = conf["published"]
    x, y = synthetic_vision(conf["n_items"], seed,
                            tuple(published["input_shape"]),
                            published["n_classes"])
    parts = partition(conf["partition"], y, conf["n_clients"], seed)
    sizes = np.asarray([len(p) for p in parts])
    padded = np.zeros((len(parts), max(int(sizes.max()), 1)), np.int32)
    for c, p in enumerate(parts):
        padded[c, :len(p)] = p
    data = {"x": jnp.asarray(x, dtype), "y": jnp.asarray(y),
            "parts": jnp.asarray(padded), "sizes": jnp.asarray(sizes)}
    del x
    n_steps = max(int(fl.get("local_steps", 1)), 1)
    if precision is None:
        precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        update = make_client_update(
            published, int(fl.get("local_epochs", 1)) * n_steps, n_steps,
            int(fl.get("batch_size", 32)),
            fl.get("compression", "none") == "int8", half_batch)
        p0 = jax.tree.map(lambda a: a.astype(dtype),
                          init_params(published, seed))

        def client(acc, start, rnd, c, lr, w):
            return update(acc, start, data["x"], data["y"], data["parts"],
                          data["sizes"], seed, rnd, c, np.asarray(lr, dtype),
                          np.asarray(w, dtype))

        if fl.get("mode", "sync") == "async":
            return _run_async(cell, fl, seed, steps, sizes, p0, client)
        return _run_sync(fl, seed, steps, sizes, p0, lanes(cell), client)


def _zeros(p):
    return jax.tree.map(jnp.zeros_like, p)


def _run_sync(fl, seed, steps, sizes, p0, lrs, client) -> dict:
    if int(fl.get("max_cohort", 0)) <= 0:
        raise NotImplementedError("the sync reference follows ragged "
                                  "cohorts (max_cohort > 0) only")
    rpl = int(fl.get("rounds_per_launch", 1))
    n_clients = int(fl["n_clients"])
    target = int(fl.get("cohort") or n_clients)
    params = [p0] * len(lrs)
    losses = [[] for _ in lrs]
    kept = [[_host(p0)] for _ in lrs]
    for rnd in range(steps * rpl):
        members = cohort(seed, rnd, n_clients, target)
        den = float(sum(sizes[c] for c in members))
        for s, lr in enumerate(lrs):
            acc, round_loss = _zeros(p0), []
            for c in members:
                acc, loss = client(acc, params[s], rnd, int(c), lr,
                                   float(sizes[c]))
                round_loss.append(loss)
            params[s] = jax.tree.map(lambda p, a: p + a / den, params[s],
                                     acc)
            losses[s].append(float(np.mean(np.asarray(round_loss))))
            if (rnd + 1) % rpl == 0 and (rnd + 1) // rpl in (1, steps):
                kept[s].append(_host(params[s]))
    return {"losses": np.asarray(losses), "params": kept}


def _run_async(cell, fl, seed, steps, sizes, p0, client) -> dict:
    per_round = int(fl.get("async_buffer", 0))
    if per_round <= 1:
        raise NotImplementedError("the async reference follows FedBuff "
                                  "(async_buffer > 1) only")
    rpl = int(fl.get("rounds_per_launch", 1))
    n_events = steps * rpl * per_round
    ev = schedule(seed, int(fl["n_clients"]), n_events, sizes, fl,
                  cell["traffic"].get("runtime", {}))
    lr = float(fl.get("client_lr", 0.1))
    ring = [p0] * ev["ring"]
    params, version = p0, 0
    acc = _zeros(p0)
    losses, kept, event_loss = [], [_host(p0)], []
    for e in range(n_events):
        stale = ring[int(ev["start"][e]) % ev["ring"]]
        # a rejected arrival trains too (its loss is logged) but adds 0
        w = float(ev["coeff"][e]) if ev["accept"][e] else 0.0
        acc, loss = client(acc, stale, int(ev["task"][e]),
                           int(ev["client"][e]), lr, w)
        event_loss.append(loss)
        if ev["apply"][e]:
            params = jax.tree.map(lambda p, a: p + a, params, acc)
            version += 1
            ring[version % ev["ring"]] = params
            acc = _zeros(p0)
        if (e + 1) % per_round == 0:
            losses.append(float(np.mean(np.asarray(
                event_loss[-per_round:]))))
        if (e + 1) % (rpl * per_round) == 0 and \
                (e + 1) // (rpl * per_round) in (1, steps):
            kept.append(_host(params))
    return {"losses": np.asarray([losses]), "params": [kept]}
