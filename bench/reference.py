"""Plain reference of the benchmark's FL algorithms: one client at a time.

It imports nothing of the program, and nothing here knows the model: the
cell's family (``bench/families/<family>.py``, named by its configuration)
derives again from the seed what the program derives of the data, the
client partition and the initial weights, and trains one client. From the
seed this module derives each round's cohort, each client's batches and,
for async cells, the virtual-clock event schedule. It trains each client in
turn through the family in float32 at the highest matrix-product
precision, quantizes its delta to int8 blocks where the cell compresses,
and applies the weighted mean (sync) or the staleness-weighted buffer flush
(FedBuff) on the server.

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


# -- parameter trees -----------------------------------------------------------

def _key(k) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def flat(tree, dtype=None) -> dict:
    """The leaves of a parameter tree on the host, by path (``layers/0/w``);
    a flat dict keeps its keys."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(_key(k) for k in path): np.asarray(leaf, dtype)
            for path, leaf in leaves}


def _roundtrip_leaf(d):
    values = d.reshape(-1).astype(jnp.float32)
    pad = (-values.shape[0]) % QBLOCK
    blocks = jnp.pad(values, (0, pad)).reshape(-1, QBLOCK)
    amax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127)
    deq = (q * scale).reshape(-1)[:values.shape[0]]
    return deq.reshape(d.shape).astype(d.dtype)


@jax.jit
def roundtrip_int8(delta):
    """Each leaf in blocks of 256 (zero-padded): q = round(x / (max|x|/127))
    clipped to +-127, sent as q and the block's float32 scale, and
    dequantized as q * scale."""
    return jax.tree.map(_roundtrip_leaf, delta)


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
    return flat(p, np.float32)


def make_client_update(train, n_steps: int, batch: int, int8: bool):
    """Jitted: one client's round, from its seed-drawn batch positions to
    its (quantized) delta added into the server's weighted sum:
    (acc, start, items, parts, sizes, seed, rnd, client, lr, w) ->
    (acc, loss). ``train`` is the family's local training."""

    def client(acc, start, items, parts, sizes, seed, rnd, c, lr, w):
        key = _fold(jax.random.PRNGKey(seed), rnd, 0xBA7C, c)
        pos = jax.random.randint(key, (n_steps, batch), 0,
                                 jnp.maximum(sizes[c], 1))
        idx = parts[c][pos]
        new, loss = train(start, jax.tree.map(lambda a: a[idx], items), lr)
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
    [before step 1, after step 1, after step ``steps``], each a flat dict
    of the parameter tree's leaves by path}."""
    from bench import families
    from bench.cells import job_seed, lanes, train_params

    conf = cell["config"]
    fam = families.of(conf)
    fl = train_params(cell)
    seed = job_seed(seed)
    items, parts = fam.data(conf, seed)
    sizes = np.asarray([len(p) for p in parts])
    padded = np.zeros((len(parts), max(int(sizes.max()), 1)), np.int32)
    for c, p in enumerate(parts):
        padded[c, :len(p)] = p
    items = {k: jnp.asarray(v, dtype) if np.issubdtype(v.dtype, np.floating)
             else jnp.asarray(v) for k, v in items.items()}
    parts, sizes_dev = jnp.asarray(padded), jnp.asarray(sizes)
    n_steps = max(int(fl.get("local_steps", 1)), 1)
    if precision is None:
        precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        train = fam.make_local_train(
            conf, int(fl.get("local_epochs", 1)) * n_steps, n_steps,
            half_batch)
        update = make_client_update(train, n_steps,
                                    int(fl.get("batch_size", 32)),
                                    fl.get("compression", "none") == "int8")
        p0 = jax.tree.map(lambda a: a.astype(dtype),
                          fam.init_params(conf, seed))

        def client(acc, start, rnd, c, lr, w):
            return update(acc, start, items, parts, sizes_dev, seed, rnd, c,
                          np.asarray(lr, dtype), np.asarray(w, dtype))

        if fl.get("mode", "sync") == "async":
            return _run_async(cell, fl, seed, steps, sizes, p0, client)
        return _run_sync(fl, seed, steps, sizes, p0, lanes(cell), client)


def _mean(losses) -> float:
    """The mean of clients' losses, in float32 whatever the dtype trained."""
    return float(np.mean(np.asarray(jnp.stack(losses), np.float32)))


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
            losses[s].append(_mean(round_loss))
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
            losses.append(_mean(event_loss[-per_round:]))
        if (e + 1) % (rpl * per_round) == 0 and \
                (e + 1) // (rpl * per_round) in (1, steps):
            kept.append(_host(params))
    return {"losses": np.asarray([losses]), "params": [kept]}
