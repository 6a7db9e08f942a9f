"""Deterministic synthetic data pipelines (FLsim Dataset contract).

Two root datasets:
- ``SyntheticVision``: CIFAR-10 / MNIST-shaped classification data with a
  planted linear-signal so models can actually learn (losses decrease and
  accuracies separate across strategies, as the paper's figures need).
- ``SyntheticLM``: token streams with an order-k Markov structure for the
  LM-family architectures.

Every pipeline exposes prepare_root_dataset / distribute_into_chunks /
client_batches with a position cursor, so checkpoints can resume the exact
data order (fault tolerance).
"""
from __future__ import annotations

import concurrent.futures
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import determinism
from repro.data import partition as part_mod
from repro.telemetry.recorder import FlightRecorder


# ---------------------------------------------------------------------------
# Device-resident staging (the "download once" half of the driver contract)
# ---------------------------------------------------------------------------

def _pad_idx(parts, lmax: int) -> np.ndarray:
    """Ragged per-client index lists -> dense (C, lmax) int32 by cyclic
    repetition. The wrap never biases sampling: gather positions are drawn
    in [0, true len), so pad columns past a client's length are never read —
    which also makes the padding width itself trajectory-invariant."""
    idx = np.zeros((len(parts), lmax), np.int32)
    for c, p in enumerate(parts):
        if len(p):
            reps = int(np.ceil(lmax / len(p)))
            idx[c] = np.concatenate([p] * reps)[:lmax]
    return idx


def stage_partitions(x, y, parts):
    """One-time device staging of the full root dataset + client partitions.

    The ragged per-client index lists are padded to a dense (C, Lmax) int32
    matrix by cyclic repetition (a client with fewer items than the pad just
    wraps; the wrap never biases sampling because the on-device gather draws
    positions modulo the *true* length). Returns a dict of device arrays:

      x    (N, ...)  root features        y    (N,)      root labels
      idx  (C, Lmax) padded item indices  len  (C,)      true partition sizes

    ``len`` doubles as the FedAvg base weight, so zero-item clients get zero
    weight automatically.
    """
    lmax = max(max((len(p) for p in parts), default=1), 1)
    lens = np.asarray([len(p) for p in parts], np.int32)
    return {"x": jnp.asarray(x), "y": jnp.asarray(y),
            "idx": jnp.asarray(_pad_idx(parts, lmax)),
            "len": jnp.asarray(lens)}


def stage_partitions_stacked(trajectories):
    """Stage S trajectories' datasets as one stacked device residency.

    ``trajectories`` is a list of (x, y, parts) triples — one per campaign
    trajectory (different seeds and/or Dirichlet alphas give different root
    data and/or partitions; identical triples are simply duplicated). All
    trajectories must share n_items and n_clients (sweeps vary distribution,
    not problem size). Returns the ``stage_partitions`` dict with a leading
    sweep dim on every leaf:

      x (S, N, ...)   y (S, N)   idx (S, C, Lmax)   len (S, C)

    Lmax is the max over trajectories; because gather positions are drawn in
    [0, len), the wider shared pad is unobservable, so lane ``s`` of the
    stacked gather is bitwise the trajectory's own single staging.
    """
    n_clients = {len(parts) for _, _, parts in trajectories}
    if len(n_clients) != 1:
        raise ValueError(f"trajectories disagree on n_clients: {n_clients}")
    lmax = max(max((max((len(p) for p in parts), default=1), 1)
                   for _, _, parts in trajectories))
    xs = np.stack([np.asarray(x) for x, _, _ in trajectories])
    ys = np.stack([np.asarray(y) for _, y, _ in trajectories])
    idx = np.stack([_pad_idx(parts, lmax) for _, _, parts in trajectories])
    lens = np.stack([np.asarray([len(p) for p in parts], np.int32)
                     for _, _, parts in trajectories])
    return {"x": jnp.asarray(xs), "y": jnp.asarray(ys),
            "idx": jnp.asarray(idx), "len": jnp.asarray(lens)}


# Per-leaf vmap axes for a deduped campaign staging: the concatenated root
# (x, y) is shared across lanes (no sweep axis), only the small per-lane
# index/length planes carry the leading (S,) dim.
DEDUP_STAGED_AXES = {"x": None, "y": None, "idx": 0, "len": 0}


def stage_partitions_dedup(trajectories, keys=None, mesh=None):
    """Stage S trajectories with the shared root datasets deduplicated.

    ``stage_partitions_stacked`` duplicates the root dataset S times even
    when every lane shares it (any scalar-only sweep) — the ROADMAP memory
    item. Here lanes that share a data-plane triple share ONE device copy:
    the unique roots concatenate along the item axis, and each lane's padded
    index matrix is offset into the concatenation, which IS the
    lane->dataset indirection — the gather functions stay untouched and the
    drawn batches are bitwise identical (positions are drawn in
    [0, true len) and the offset just relocates the same bytes). Returns
    ``(staged, lane_ds)``:

      x ((sum_u N_u), ...)  y ((sum_u N_u),)   shared concatenated roots
      idx (S, C, Lmax)      len (S, C)          per-lane (offset) planes

    plus ``lane_ds`` (S,) int32 mapping each lane to its unique dataset (for
    introspection/tests; the indirection itself is baked into ``idx``).
    ``keys`` are optional hashable dedup keys per trajectory (the campaign
    passes its staging-cache keys); identity is the default.

    ``mesh`` (a ``launch/mesh.lane_mesh``) places the staging for a
    device-parallel campaign: the concatenated roots replicate on every
    device, the per-lane ``idx``/``len`` planes shard their leading (S,)
    dim over the ``lanes`` axis — exactly ``DEDUP_STAGED_AXES`` rendered
    as a sharding. S must then be a multiple of the lane count (the
    campaign pads with dead lanes before staging).
    """
    keys = list(keys) if keys is not None else [id(t) for t in trajectories]
    if len(keys) != len(trajectories):
        raise ValueError(f"{len(keys)} dedup keys for "
                         f"{len(trajectories)} trajectories")
    n_clients = {len(parts) for _, _, parts in trajectories}
    if len(n_clients) != 1:
        raise ValueError(f"trajectories disagree on n_clients: {n_clients}")
    uniq: dict = {}
    roots = []
    for k, t in zip(keys, trajectories):
        if k not in uniq:
            uniq[k] = len(roots)
            roots.append(t)
    lane_ds = np.asarray([uniq[k] for k in keys], np.int32)
    lmax = max(max((max((len(p) for p in parts), default=1), 1)
                   for _, _, parts in roots))
    offsets = np.concatenate(
        [[0], np.cumsum([np.asarray(x).shape[0] for x, _, _ in roots])])
    x_cat = np.concatenate([np.asarray(x) for x, _, _ in roots])
    y_cat = np.concatenate([np.asarray(y) for _, y, _ in roots])
    pads = [_pad_idx(parts, lmax) + np.int32(offsets[u])
            for u, (_, _, parts) in enumerate(roots)]
    lens = [np.asarray([len(p) for p in parts], np.int32)
            for _, _, parts in roots]
    staged = {"x": x_cat, "y": y_cat,
              "idx": np.stack([pads[u] for u in lane_ds]),
              "len": np.stack([lens[u] for u in lane_ds])}
    if mesh is not None:
        from repro.launch.mesh import shard_lanes
        staged = shard_lanes(staged, mesh, DEDUP_STAGED_AXES)
    else:
        staged = {k: jnp.asarray(v) for k, v in staged.items()}
    return staged, lane_ds


@jax.named_scope("fl.gather")
def gather_one_client_batch(staged, round_key, client, batch_size: int,
                            n_steps: int):
    """Jittable batch gather for a single (possibly traced) client id.

    Positions are drawn uniformly (with replacement) from the client's true
    partition via ``determinism.batch_key(round_key, client)``, so the batch
    stream for a given (seed, round) is identical no matter how rounds (or
    async events) are chunked into launches. The sync driver vmaps this over
    all clients; the async event scan calls it per arriving client — the two
    are bitwise-identical lanes because threefry draws are
    vectorization-invariant. Returns {"x": (n_steps, B, ...), "y": ...}.
    """
    key = determinism.batch_key(round_key, client)
    maxv = jnp.maximum(staged["len"][client], 1)
    pos = jax.random.randint(key, (n_steps, batch_size), 0, maxv)
    sel = staged["idx"][client, pos]
    return {"x": staged["x"][sel], "y": staged["y"][sel]}


def gather_client_batches(staged, round_key, batch_size: int, n_steps: int):
    """Jittable per-round batch gather for every client, on device.

    One vmap over ``gather_one_client_batch`` (the single source of truth
    for the position draw). Returns {"x": (C, n_steps, B, ...), "y": ...}.
    """
    n_clients = staged["idx"].shape[0]
    return jax.vmap(
        lambda c: gather_one_client_batch(staged, round_key, c, batch_size,
                                          n_steps))(jnp.arange(n_clients))


# ---------------------------------------------------------------------------
# Ragged client plane: cohort slabs + streaming (double-buffered) staging
# ---------------------------------------------------------------------------
#
# With ``max_cohort > 0`` the compiled scan no longer sees the population:
# each round consumes one *slab row* — the sampled cohort's data padded to K
# = max_cohort slots, with the tail zero-weighted. The host replays
# ``faults.cohort_mask`` (already the bitwise host==program contract) ahead
# of the launch, so it knows exactly which clients' shards each chunk needs:
# one compiled, vmapped call per chunk (``faults.cohort_masks``) on the
# program's own backend, read back to the host once.
# Two stagers assemble slabs for the SAME compiled program:
#
#   ResidentSlabStager   — root staged on device once, slab gathered on
#                          device per chunk (an async dispatch).
#   StreamingSlabStager  — only the sampled cohorts' shards ever leave host
#                          memory; chunk k+1's host gather + host->device
#                          copy run on a background thread overlapped with
#                          chunk k's scan (double buffering).
#
# Because both feed identical slab bytes into one program, streaming ==
# resident is bitwise by construction, and a population far larger than
# device memory trains at a working set bounded by (rounds_per_launch, K,
# Lmax) — the ``staged_bytes`` telemetry counters report it per chunk.


def slab_nbytes(slab) -> int:
    """Total bytes of a slab (or any pytree of arrays)."""
    return int(sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(slab)))


@jax.named_scope("fl.gather")
def gather_slab_batches(slab_row, round_key, batch_size: int, n_steps: int):
    """Jittable per-round batch gather from one cohort slab row.

    The slab analogue of ``gather_client_batches``: slot ``k`` draws
    positions in [0, true len) via ``determinism.batch_key(round_key,
    cid[k])`` — keyed by the *real* client id, not the slot — so a client's
    byte stream is invariant to which slot it lands in and to the slab pad
    width Lmax (pad columns are never read). Returns
    {"x": (K, n_steps, B, ...), "y": ...}.
    """
    def one(k):
        key = determinism.batch_key(round_key, slab_row["cid"][k])
        maxv = jnp.maximum(slab_row["len"][k], 1)
        pos = jax.random.randint(key, (n_steps, batch_size), 0, maxv)
        return {"x": slab_row["x"][k][pos], "y": slab_row["y"][k][pos]}
    return jax.vmap(one)(jnp.arange(slab_row["len"].shape[0]))


@jax.named_scope("fl.gather")
def gather_event_batch(row, round_key, client, batch_size: int, n_steps: int):
    """Jittable batch gather from one async event's slab row.

    Same position draw as ``gather_one_client_batch`` (keyed on the real
    client id carried by the schedule), reading the event's staged shard
    instead of the resident root.
    """
    key = determinism.batch_key(round_key, client)
    maxv = jnp.maximum(row["len"], 1)
    pos = jax.random.randint(key, (n_steps, batch_size), 0, maxv)
    return {"x": row["x"][pos], "y": row["y"][pos]}


class _Prefetcher:
    """Single-slot double buffer: one background thread assembles the next
    chunk's slab while the device runs the current one. A request that does
    not match the pending prefetch (resume, end-of-run remainder) just
    assembles synchronously."""

    # the executor's flight recorder and track (``set_recorder``); a
    # disabled recorder until then, so the spans cost nothing
    recorder = FlightRecorder(enabled=False)
    track = "run"

    def __init__(self):
        self.peak_slab_bytes = 0
        self._pool = None
        self._pending = None

    def set_recorder(self, recorder, track: str = "run") -> None:
        """Record this stager's spans (``cohort_plan``) on ``recorder``."""
        self.recorder, self.track = recorder, track

    def _submit(self, key, fn):
        if self._pool is None:
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="slab-stager")
        self._pending = (key, self._pool.submit(fn))

    def _take(self, key, fn):
        pend, self._pending = self._pending, None
        if pend is not None and pend[0] == key:
            out = pend[1].result()
        else:
            if pend is not None:
                pend[1].cancel()
            out = fn()
        self.peak_slab_bytes = max(self.peak_slab_bytes, slab_nbytes(out))
        return out


class SlabStager(_Prefetcher):
    """Base cohort-slab stager: host-side cohort planning shared by the
    resident and streaming backends.

    A slab for a chunk of ``n`` rounds starting at absolute round ``start``
    is a dict of scan inputs with leading round dim n:

      x   (n, K, Lmax, ...)  slot features     y   (n, K, Lmax)  slot labels
      len (n, K)  true shard sizes             cid (n, K)        real client ids
      w   (n, K)  FedAvg base weight (len) * cohort mask, 0 on pad slots

    Kept clients fill slots in ascending-id order; pad slots repeat the
    first kept client's shard (zero-weighted, and harmless to train on).
    """

    def __init__(self, fl, fault):
        super().__init__()
        from repro.runtime import faults as faults_mod
        self.fl = fl
        self.fault = fault if fault is not None else faults_mod.FaultModel()
        self.k_slots = int(fl.max_cohort)
        self.lmax = 1
        self.lens = np.zeros((fl.n_clients,), np.int32)

    def plan(self, start: int, n: int):
        """Replay the cohort draw for rounds [start, start+n) on the host.

        Returns (slots (n, K) int32, real (n, K) float32) — exactly the
        clients ``faults.cohort_mask`` keeps inside the compiled program,
        because ``faults.cohort_masks`` vmaps the same function over the
        chunk's rounds in one compiled call, read back once. Runs under a
        ``cohort_plan`` span and records a ``cohort_plan_batch`` counter
        (``compiled`` is 1 when the call compiled a new plan program).
        """
        from repro.runtime import faults as faults_mod
        fl = self.fl
        target = int(fl.cohort or fl.n_clients)
        slots = np.zeros((n, self.k_slots), np.int32)
        real = np.zeros((n, self.k_slots), np.float32)
        with self.recorder.span("cohort_plan", track=self.track):
            programs = faults_mod.cohort_mask_programs()
            masks = np.asarray(faults_mod.cohort_masks(
                self.fault, np.arange(start, start + n), fl.n_clients,
                target, fl.straggler_overprovision))
            compiled = faults_mod.cohort_mask_programs() > programs
            for i, mask in enumerate(masks):
                kept = np.flatnonzero(mask > 0)
                if len(kept) > self.k_slots:
                    raise ValueError(
                        f"round {start + i} kept {len(kept)} clients but "
                        f"max_cohort={self.k_slots} slots are staged")
                slots[i] = kept[0] if len(kept) else 0
                slots[i, :len(kept)] = kept
                real[i, :len(kept)] = 1.0
            self.recorder.counter("cohort_plan_batch", track=self.track,
                                  rounds=n, compiled=int(compiled))
        return slots, real

    def widen(self, lmax: int) -> None:
        """Re-pad shards to a wider Lmax (campaign lanes share one width)."""
        self.lmax = max(self.lmax, int(lmax))

    def slab(self, start: int, n: int):
        """The chunk's slab on device (from the prefetch buffer if it hit)."""
        return self._take(("sync", start, n),
                          lambda: self._assemble_chunk(start, n))

    def prefetch(self, start: int, n: int) -> None:
        """Kick background assembly of the next chunk's slab."""
        if n > 0:
            self._submit(("sync", start, n),
                         lambda: self._assemble_chunk(start, n))

    def event_slab(self, clients, tag):
        """Per-event slab rows {"x": (E, Lmax, ...), "y", "len"} for the
        async drivers; ``tag`` keys the prefetch buffer (event window)."""
        clients = np.asarray(clients, np.int32)
        return self._take(("ev", tag),
                          lambda: self._assemble_events(clients))

    def prefetch_events(self, clients, tag) -> None:
        """Kick background assembly of the next event window's rows."""
        clients = np.asarray(clients, np.int32)
        if len(clients):
            self._submit(("ev", tag), lambda: self._assemble_events(clients))

    def _assemble_chunk(self, start, n):
        slots, real = self.plan(start, n)
        return self._assemble(slots, real)


class ResidentSlabStager(SlabStager):
    """Slab stager over a device-resident root: ``stage_partitions`` once,
    then each chunk's slab is an on-device gather (asynchronously
    dispatched, so no prefetch thread is needed)."""

    def __init__(self, x, y, parts, fl, fault):
        super().__init__(fl, fault)
        self._parts = parts
        self.staged = stage_partitions(x, y, parts)
        self.lmax = int(self.staged["idx"].shape[1])
        self.lens = np.asarray(self.staged["len"])
        self.data = (np.asarray(x), np.asarray(y), parts)
        self.resident_bytes = slab_nbytes(self.staged)
        self.device_bytes = self.resident_bytes

    def widen(self, lmax: int) -> None:
        """Re-pad the resident index plane to a wider Lmax."""
        if int(lmax) > self.lmax:
            self.lmax = int(lmax)
            self.staged["idx"] = jnp.asarray(_pad_idx(self._parts, self.lmax))

    def prefetch(self, start: int, n: int) -> None:
        """No-op: the device gather in ``slab`` is already async."""

    def prefetch_events(self, clients, tag) -> None:
        """No-op: the device gather in ``event_slab`` is already async."""

    def _assemble(self, slots, real):
        sl = jnp.asarray(slots)
        idx = self.staged["idx"][sl]                     # (n, K, Lmax)
        lens = self.staged["len"][sl]
        return {"x": self.staged["x"][idx], "y": self.staged["y"][idx],
                "len": lens, "cid": sl,
                "w": lens.astype(jnp.float32) * jnp.asarray(real)}

    def _assemble_events(self, clients):
        cl = jnp.asarray(clients)
        idx = self.staged["idx"][cl]                     # (E, Lmax)
        return {"x": self.staged["x"][idx], "y": self.staged["y"][idx],
                "len": self.staged["len"][cl]}


class StreamingSlabStager(SlabStager):
    """Slab stager that never stages the population: per-client shards come
    from a host-side factory and only the sampled cohorts' shards are
    gathered (numpy) and copied to device, double-buffered by the inherited
    prefetch thread.

    ``shard_fn(cid) -> (x_c (l, ...), y_c (l,))`` must be deterministic; a
    ``SyntheticPopulation`` generates shards on demand, and
    ``from_partitions`` wraps an in-memory root so streaming can be checked
    bitwise against ``ResidentSlabStager`` on configs that fit.
    """

    def __init__(self, shard_fn, fl, fault, lens, lmax=None):
        super().__init__(fl, fault)
        self._shard = shard_fn
        self.lens = np.asarray(lens, np.int32)
        if len(self.lens) != fl.n_clients:
            raise ValueError(f"{len(self.lens)} shard lengths for "
                             f"n_clients={fl.n_clients}")
        self.lmax = int(lmax) if lmax else max(int(self.lens.max()), 1)
        x0, y0 = shard_fn(0)
        x0, y0 = np.asarray(x0), np.asarray(y0)
        self._item_shape, self._x_dtype = x0.shape[1:], x0.dtype
        self._y_dtype = y0.dtype
        item = int(np.prod(self._item_shape, dtype=np.int64))
        # What full residency would cost: the honest denominator for the
        # bench's staged-bytes ceiling (pad to Lmax like stage_partitions,
        # plus the int32 index/len planes it would carry).
        c = int(fl.n_clients)
        self.resident_bytes = int(
            c * self.lmax * (item * self._x_dtype.itemsize
                             + self._y_dtype.itemsize + 4) + c * 4)
        self.device_bytes = 0

    @classmethod
    def from_partitions(cls, x, y, parts, fl, fault):
        """Streaming view of an in-memory root: shard c is x[parts[c]].

        An empty partition reads root item 0 (mirroring ``_pad_idx``'s
        zero rows) so the assembled slab is byte-identical to the resident
        stager's device gather.
        """
        x, y = np.asarray(x), np.asarray(y)

        def shard(c):
            p = np.asarray(parts[c], np.int64)
            return (x[p], y[p]) if len(p) else (x[:1], y[:1])

        lens = np.asarray([len(p) for p in parts], np.int32)
        st = cls(shard, fl, fault, lens=lens)
        st.data = (x, y, parts)
        return st

    def _padded_shard(self, c):
        xc, yc = self._shard(int(c))
        xc, yc = np.asarray(xc), np.asarray(yc)
        length = max(len(yc), 1)
        reps = -(-self.lmax // length)
        sel = np.concatenate([np.arange(length, dtype=np.int64)] * reps)
        sel = sel[:self.lmax]
        return xc[sel], yc[sel]

    def _assemble(self, slots, real):
        n, k = slots.shape
        sx = np.empty((n, k, self.lmax) + self._item_shape, self._x_dtype)
        sy = np.empty((n, k, self.lmax), self._y_dtype)
        cache = {}
        for i in range(n):
            for j in range(k):
                c = int(slots[i, j])
                if c not in cache:
                    cache[c] = self._padded_shard(c)
                sx[i, j], sy[i, j] = cache[c]
        host = {"x": sx, "y": sy, "len": self.lens[slots],
                "cid": slots, "w": self.lens[slots].astype(np.float32) * real}
        return {key: jnp.asarray(v) for key, v in host.items()}

    def _assemble_events(self, clients):
        e = len(clients)
        sx = np.empty((e, self.lmax) + self._item_shape, self._x_dtype)
        sy = np.empty((e, self.lmax), self._y_dtype)
        cache = {}
        for i, c in enumerate(np.asarray(clients)):
            c = int(c)
            if c not in cache:
                cache[c] = self._padded_shard(c)
            sx[i], sy[i] = cache[c]
        return {"x": jnp.asarray(sx), "y": jnp.asarray(sy),
                "len": jnp.asarray(self.lens[clients])}


class StackedSlabStager(_Prefetcher):
    """Campaign-plane stager: one slab stager per lane, stacked to a leading
    (S,) sweep dim so the vmapped ragged scan consumes it with in_axes=0.

    Lanes are widened to a common Lmax up front; the wider pad is
    unobservable (gather positions stay in [0, len)), so lane ``s`` of the
    stacked slab trains bitwise like the lane's own single run.
    """

    def __init__(self, lanes):
        super().__init__()
        self.lanes = list(lanes)
        self.lmax = max(l.lmax for l in self.lanes)
        for lane in self.lanes:
            lane.widen(self.lmax)
        self.streaming = any(isinstance(l, StreamingSlabStager)
                             for l in self.lanes)
        self.resident_bytes = sum(l.resident_bytes for l in self.lanes)
        self.device_bytes = sum(l.device_bytes for l in self.lanes)

    def set_recorder(self, recorder, track: str = "run") -> None:
        """Record the stack's and every lane's spans on ``recorder``."""
        super().set_recorder(recorder, track)
        for lane in self.lanes:
            lane.set_recorder(recorder, track)

    def slab(self, start: int, n: int):
        """The chunk's stacked (S, n, K, ...) slab on device."""
        return self._take(("sync", start, n),
                          lambda: self._assemble_chunk(start, n))

    def prefetch(self, start: int, n: int) -> None:
        """Background-assemble the next chunk across all streaming lanes."""
        if n > 0 and self.streaming:
            self._submit(("sync", start, n),
                         lambda: self._assemble_chunk(start, n))

    def _assemble_chunk(self, start, n):
        if self.streaming:
            rows = []
            for lane in self.lanes:
                slots, real = lane.plan(start, n)
                rows.append({k: np.asarray(v)
                             for k, v in lane._assemble(slots, real).items()})
            host = {k: np.stack([r[k] for r in rows]) for k in rows[0]}
            return {k: jnp.asarray(v) for k, v in host.items()}
        return jax.tree.map(lambda *ls: jnp.stack(ls),
                            *[lane._assemble_chunk(start, n)
                              for lane in self.lanes])


def make_slab_stager(dataset, fl, fault):
    """Build the right slab stager for a ragged-mode job.

    Datasets exposing the population protocol (a ``shard(cid)`` factory,
    e.g. ``SyntheticPopulation``) are never materialized and require
    ``streaming: true``; in-memory roots stage resident by default and
    stream when asked.
    """
    if hasattr(dataset, "shard"):
        if not fl.streaming:
            raise ValueError(
                f"{type(dataset).__name__} generates shards on demand and "
                "cannot be staged resident — set streaming: true")
        if int(dataset.n_clients) != int(fl.n_clients):
            raise ValueError(f"dataset population ({dataset.n_clients}) != "
                             f"fl.n_clients ({fl.n_clients})")
        lens = np.full(fl.n_clients, int(dataset.items_per_client), np.int32)
        return StreamingSlabStager(dataset.shard, fl, fault, lens=lens)
    x, y, parts = dataset.distribute_into_chunks(
        fl.partition, fl.n_clients, fl.dirichlet_alpha)
    if fl.streaming:
        return StreamingSlabStager.from_partitions(x, y, parts, fl, fault)
    return ResidentSlabStager(x, y, parts, fl, fault)


@dataclasses.dataclass
class SyntheticPopulation:
    """A large client population materialized one shard at a time.

    The streaming-plane exemplar: ``shard(cid)`` deterministically generates
    client ``cid``'s few items from (seed, cid) with the same planted
    class-prototype signal as ``SyntheticVision``, so a 10^5-client
    population costs zero host memory until a cohort is actually sampled.
    """

    n_clients: int = 100_000
    items_per_client: int = 8
    shape: tuple = (8, 8, 1)
    n_classes: int = 10
    seed: int = 0
    noise: float = 0.8

    def __post_init__(self):
        """Lazily-built prototype cache (shared across shards)."""
        self._protos = None

    def shard(self, cid: int):
        """Client ``cid``'s shard as (x (l, ...), y (l,)) numpy arrays."""
        if self._protos is None:
            rng0 = np.random.RandomState(self.seed)
            self._protos = rng0.randn(
                self.n_classes, *self.shape).astype(np.float32)
        rng = np.random.RandomState(
            (1_000_003 * (self.seed + 1) + int(cid)) % (2 ** 31 - 1))
        y = rng.randint(0, self.n_classes, self.items_per_client)
        x = self._protos[y] + self.noise * rng.randn(
            self.items_per_client, *self.shape).astype(np.float32)
        return x.astype(np.float32), y


@dataclasses.dataclass
class SyntheticVision:
    """Deterministic synthetic image classification dataset family."""
    n_items: int = 2048
    shape: tuple = (32, 32, 3)
    n_classes: int = 10
    seed: int = 0
    noise: float = 0.8

    def prepare_root_dataset(self):
        """Generate the root ``(x, y)`` arrays for the configured size."""
        rng = np.random.RandomState(self.seed)
        y = rng.randint(0, self.n_classes, self.n_items)
        protos = rng.randn(self.n_classes, *self.shape).astype(np.float32)
        x = protos[y] + self.noise * rng.randn(
            self.n_items, *self.shape).astype(np.float32)
        return x, y

    def distribute_into_chunks(self, kind: str, n_clients: int,
                               alpha: float = 0.5):
        """Partition the root set; returns ``(x, y, per-client index lists)``."""
        x, y = self.prepare_root_dataset()
        parts = part_mod.partition(kind, y, n_clients, alpha, self.seed)
        return x, y, parts

    @staticmethod
    def client_batches(x, y, idx, batch_size: int, n_steps: int, seed: int,
                       cursor: int = 0):
        """Deterministic batches for one client; returns (batches, cursor)."""
        rng = np.random.RandomState(seed)
        order = idx[rng.permutation(len(idx))]
        reps = int(np.ceil((cursor + n_steps * batch_size) / max(len(order), 1)))
        stream = np.concatenate([order] * max(reps, 1))
        sel = stream[cursor:cursor + n_steps * batch_size]
        sel = sel.reshape(n_steps, batch_size)
        batches = {"x": x[sel], "y": y[sel]}
        return batches, cursor + n_steps * batch_size


@dataclasses.dataclass
class SyntheticLM:
    """Deterministic synthetic next-token LM dataset family."""
    vocab: int = 512
    seed: int = 0

    def tokens(self, batch: int, seq: int, salt: int = 0):
        """Markov-ish token stream: next token depends on previous one."""
        rng = np.random.RandomState(self.seed + salt)
        trans = rng.permutation(self.vocab)
        toks = np.zeros((batch, seq + 1), np.int32)
        toks[:, 0] = rng.randint(0, self.vocab, batch)
        noise = rng.rand(batch, seq)
        rand_tok = rng.randint(0, self.vocab, (batch, seq))
        for t in range(seq):
            nxt = trans[toks[:, t]]
            toks[:, t + 1] = np.where(noise[:, t] < 0.75, nxt, rand_tok[:, t])
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def client_batches(self, client_id: int, n_steps: int, batch: int,
                       seq: int, round_idx: int = 0):
        """Return ``n_steps`` stacked token batches for one client-round."""
        out = [self.tokens(batch, seq, salt=client_id * 100003 + round_idx * 7 + s)
               for s in range(n_steps)]
        return {k: np.stack([o[k] for o in out]) for k in out[0]}
