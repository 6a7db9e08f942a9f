"""Campaign executor — S sweep trajectories in ONE compiled program.

The paper's headline is streamlined benchmarking of "a plethora" of FL
experiments from job configs; a multi-seed, multi-alpha comparison used to
cost S sequential runs of the Executor. Here the *trajectory* becomes a
batch axis: ``core/sweeps.py`` expands the job's ``sweep:`` section into S
per-trajectory configs split into a data plane (unique root datasets staged
once and shared via an offset-index indirection — scalar-only sweeps no
longer duplicate the dataset S times; per-lane ``idx``/``len`` planes carry
the ``(S,)`` dim), a schedule plane (async schedules stacked to ``(S, E)``)
and a scalar plane (traced ``(S,)`` knob arrays threaded through
``rounds.bind_hyper``), and ``CampaignExecutor`` wraps the *same* sync round
scan / async event scan the single-run Executor compiles in an outer
``jax.vmap``. One launch advances all S trajectories; the host chunk loop,
checkpoint/ledger/eval boundary I/O, and the bitwise chunking contract are
inherited from ``Executor``.

One executor serves one *program signature* (``core/plan.py``): every lane
must trace to the job's compiled program. Heterogeneous sweeps (categorical
axes — strategy/topology/placement/mode/async_buffer) go through the
planner, which buckets lanes by signature and instantiates one
``CampaignExecutor`` per bucket via the ``lanes`` override
(``runtime/scheduler.py::PlanExecutor``).

The lane scheduler's per-lane ``alive`` mask threads into the compiled
program as a runtime value alongside the scalar plane: a dropped lane's
state freezes (``rounds.freeze_unless``) with **no recompilation**, its
rows stop landing in the results table, and its ledger blocks stop.

``lane_devices = n`` shards the sweep axis over an n-device lane mesh
(``launch/mesh.lane_mesh``): lanes are embarrassingly parallel, so the
leading (S,) dim of every plane — data ``idx``/``len``, schedules, scalars,
alive mask, stacked model state — carries a
``jax.sharding.NamedSharding`` over ``lanes`` while the concatenated data
roots and unique schedules replicate, and the *same* compiled vmap program
partitions into n zero-collective shards. S pads up to a multiple of n
with dead lanes (``alive = 0`` from launch 1, so padding is the same
maskwork as a scheduler drop — ``freeze_unless``, no recompilation) and
padded lanes never reach the results table, the ledger, or eval. The
schedule plane also dedups (satellite): async lanes sharing
(seed, system model, staleness knobs) share ONE (E,) schedule on device,
indexed per lane like the data roots.

Determinism contract (tests/test_sweeps.py, tests/test_plan.py): lane ``s``
of a campaign is **bitwise identical** to an independent single run of the
s-th expanded config — threefry draws are vectorization-invariant (the same
argument ``gather_client_batches`` relies on), the offset gather relocates
identical bytes, the stacked pads are unobservable, the scalar plane only
swaps Python floats for equal-valued traced f32s, and the alive select is
the bitwise identity for alive lanes. Chunked == unchunked also holds under
the sweep axis, so campaigns checkpoint/resume like single runs (the
stacked state is one pytree).

Results land in a tidy table keyed by sweep coordinates (one row per
trajectory per round) — ``campaign.csv`` always (appended per chunk, not
rewritten: O(S*R) total, not O(S*R^2)), ``campaign.parquet`` when
pandas+pyarrow are importable; ``benchmarks/figures.campaign_curves`` draws
multi-seed mean±band curves from it.
"""
from __future__ import annotations

import csv
import dataclasses
import pathlib
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sweeps
from repro.core.blockchain import param_digest
from repro.core.jobs import make_dataset, make_fault, validate_cohort
from repro.core.plan import program_signature
from repro.core.probes import PROBE_NAMES
from repro.core.rounds import init_state
from repro.data.pipeline import (DEDUP_STAGED_AXES, StackedSlabStager,
                                 make_slab_stager, stage_partitions_dedup)
from repro.launch.mesh import lane_mesh, shard_lanes
from repro.runtime.executor import Executor
from repro.telemetry import comms as comms_mod

_INT_COLS = ("seed", "traj", "round", "bucket", "lane", "async_buffer")


def _parse_cell(k: str, v: str):
    if k in _INT_COLS:
        return int(float(v))
    try:
        return float(v)
    except ValueError:
        return v                        # categorical coords stay strings


def read_results(csv_path) -> list:
    """Read a campaign.csv back into tidy rows (numbers where numeric,
    categorical coordinates as strings); blank cells (eval columns off the
    chunk tails) are dropped. The single parser for the campaign table —
    resume and figures both use it."""
    with open(csv_path, newline="") as f:
        return [{k: _parse_cell(k, v) for k, v in row.items() if v != ""}
                for row in csv.DictReader(f)]


def table_columns(rows, lead) -> list:
    """The tidy table's column order: lead columns, then the rest sorted."""
    return list(lead) + sorted({k for r in rows for k in r} - set(lead))


def write_parquet(rows, lead, out_dir):
    """Best-effort ``campaign.parquet`` next to the CSV (pandas+pyarrow
    optional; the CSV is the portable artifact). One helper for the
    single-campaign and merged-plan tables so their schemas cannot
    drift."""
    try:
        import pandas as pd
        pd.DataFrame(rows, columns=table_columns(rows, lead)).to_parquet(
            pathlib.Path(out_dir) / "campaign.parquet")
    except Exception:
        pass


class AppendTable:
    """Append-only tidy CSV writer.

    The PR 3 executor rewrote the whole table at every chunk boundary —
    O(S*R^2) rows written over a campaign. Here a chunk appends only its new
    rows; a full rewrite happens only when the column set changes (in
    practice: the first flush, and a resume re-adopting a prior table).
    ``appends``/``rewrites`` are the instrumentation the satellite test
    asserts on.
    """

    def __init__(self, path):
        self.path = pathlib.Path(path)
        self.appends = 0
        self.rewrites = 0
        self._fieldnames = None
        self._written = 0

    def reset(self):
        """Forget on-disk state (next flush rewrites) — the resume path."""
        self._fieldnames = None
        self._written = 0

    def flush(self, rows, lead):
        """Bring the CSV up to date with ``rows`` (lead columns first).
        The steady-state path only inspects the rows added since the last
        flush — per-boundary cost is O(new), not O(total) — and falls back
        to a full rewrite only when a new column appears."""
        new = rows[self._written:]
        self.path.parent.mkdir(parents=True, exist_ok=True)
        if (self._fieldnames is not None and self.path.exists()
                and self._written):
            grown = {k for r in new for k in r} - set(self._fieldnames)
            if not grown:
                if new:
                    with open(self.path, "a", newline="") as f:
                        csv.DictWriter(f,
                                       fieldnames=self._fieldnames
                                       ).writerows(new)
                    self.appends += 1
                self._written = len(rows)
                return self.path
        keys = table_columns(rows, lead)
        with open(self.path, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=keys)
            w.writeheader()
            w.writerows(rows)
        self.rewrites += 1
        self._fieldnames = keys
        self._written = len(rows)
        return self.path


@dataclasses.dataclass
class CampaignExecutor(Executor):
    """Executor over the sweep axis: same compiled programs, outer vmap.

    ``job`` must carry a ``sweep:`` section (``job.sweep``) — or the planner
    passes an explicit ``lanes=(coords, fls)`` subset (one signature
    bucket). ``eval_fn`` keeps the single-run signature ``params -> dict``
    and is applied per trajectory lane. ``out_dir`` (if set) receives the
    results table at every chunk boundary.
    """
    out_dir: Optional[str] = None
    lanes: Optional[tuple] = None     # (coords, fls) bucket override
    parquet: bool = True              # planner buckets defer to the merge
    # Thread the per-lane alive mask through the compiled programs. The
    # planner sets this when a lane scheduler is attached; without one the
    # mask (and its per-round state select) stays out of the program
    # entirely, so scheduler-off campaigns pay nothing for schedulability.
    lane_scheduling: bool = False
    # Shard the sweep axis over this many devices (launch/mesh.lane_mesh);
    # a configs.base.MeshConfig is also accepted (its `lanes` axis).
    # 0 keeps the single-device vmap. S pads up to a multiple with dead
    # lanes, which threads the alive mask even scheduler-off (the pad is
    # maskwork, not recompilation).
    lane_devices: int = 0

    def __post_init__(self):
        if self.job.sweep is None:
            raise ValueError("CampaignExecutor needs a job with a sweep: "
                             "section (see core/sweeps.py for the axes)")
        self.spec = self.job.sweep
        if self.lanes is not None:
            self.coords = list(self.lanes[0])
            self.fls = list(self.lanes[1])
        else:
            self.coords = self.spec.coords()
            self.fls = sweeps.expand(self.job.fl, self.spec)
        sigs = {program_signature(f, self.job.arch) for f in self.fls}
        sigs.add(program_signature(self.job.fl, self.job.arch))
        if len(sigs) > 1:
            raise ValueError(
                "CampaignExecutor lanes span multiple program signatures "
                f"({len(sigs)}); heterogeneous sweeps (categorical axes "
                f"{self.spec.categorical_names}) must go through the "
                "planner: runtime.scheduler.PlanExecutor")
        self.S = len(self.fls)
        # a MeshConfig's `lanes` axis is an accepted spelling of the count;
        # its lanes=1 default means "no lane axis" (matching its shape/axes
        # properties), i.e. the single-device vmap, not a 1-device mesh
        if hasattr(self.lane_devices, "lanes"):
            self.lane_devices = (self.lane_devices.lanes
                                 if self.lane_devices.lanes > 1 else 0)
        self.lane_devices = int(self.lane_devices)
        self.mesh = lane_mesh(self.lane_devices) if self.lane_devices else None
        # pad S to a multiple of the device count with dead lanes (clones of
        # the last config: zero extra staged bytes through the dedup caches)
        d = max(self.lane_devices, 1)
        self.S_pad = -(-self.S // d) * d
        self._fls_pad = list(self.fls) + \
            [self.fls[-1]] * (self.S_pad - self.S)
        if self.job.fl.max_cohort > 0:
            # ragged client plane: cohort/population sizes are host-side
            # slab-plan values, so validate every lane's draw up front (a
            # lane sweeping cohort past n_clients must fail at build, not
            # silently clamp mid-campaign)
            for fl_s in self._fls_pad:
                validate_cohort(fl_s)
            if self.job.fl.mode == "async":
                raise NotImplementedError(
                    "ragged campaigns (max_cohort > 0) support sync mode "
                    "only: the async event schedule sizes by n_clients, "
                    "which the ragged plane makes a per-lane host value. "
                    "Run async ragged lanes as single Executors")
            if self.lane_devices:
                raise NotImplementedError(
                    "ragged campaigns (max_cohort > 0) do not shard over a "
                    "lane mesh yet: the stacked slab is restaged per chunk "
                    "on the host, which would break the zero-collective "
                    "lane-sharding contract. Use lane_devices=0")
        self.alive = np.ones(self.S_pad, np.float32)  # scheduler + pad mask
        self.alive[self.S:] = 0.0                     # pad lanes never run
        self._thread_alive = self.lane_scheduling or self.S_pad > self.S
        self._hyper_launch = None     # cached hyper+alive (device) dict
        self.results = []              # tidy rows: coords + traj/round/metrics
        self._tail_rows = []           # (lane, row) pairs, last round/lane
        self._table = (AppendTable(pathlib.Path(self.out_dir) /
                                   "campaign.csv")
                       if self.out_dir else None)
        super().__post_init__()

    # -- lane scheduler interface -----------------------------------------
    def drop_lane(self, s: int):
        """Zero-weight lane ``s`` from the next launch on: its state
        freezes inside the already-compiled program (the alive mask is a
        runtime input) and it stops producing table rows and ledger blocks.
        The planner keeps the lane -> drop-round record
        (``PlanExecutor.dropped``)."""
        if not self.lane_scheduling:
            raise RuntimeError(
                "drop_lane needs lane_scheduling=True at construction (the "
                "alive mask must be in the compiled program from launch 1 "
                "for a mid-campaign drop not to recompile it)")
        self.alive[s] = 0.0
        self._hyper_launch = None     # next launch re-stages the mask

    def alive_lanes(self):
        return [s for s in range(self.S) if self.alive[s] > 0]

    # -- scaffold hooks: deduped staging + vmapped init --------------------
    def _stage_data(self):
        """Data plane: restage per distinct (seed, partition, alpha);
        lanes sharing a triple share ONE staged root on device (the padded
        index matrices carry the lane->dataset indirection as offsets into
        the concatenated roots, so every lane's gather stays bitwise a
        single run's). Also builds the scalar plane + per-trajectory roots.
        ``self.data`` is the list of per-trajectory (x, y, parts) host
        views (eval_fn consumers index it by lane). Under a lane mesh the
        per-lane planes shard over ``lanes`` and the concatenated roots
        replicate (``stage_partitions_dedup(mesh=...)``)."""
        cfg = getattr(self.job.model, "cfg", None)
        if self.job.fl.max_cohort > 0:
            self._stage_ragged(cfg)
            return
        cache, trajs, keys = {}, [], []
        for fl_s in self._fls_pad:
            k = (fl_s.seed, fl_s.partition, fl_s.dirichlet_alpha)
            if k not in cache:
                ds = make_dataset(self.job.raw, fl_s, cfg)
                cache[k] = ds.distribute_into_chunks(
                    fl_s.partition, fl_s.n_clients, fl_s.dirichlet_alpha)
            trajs.append(cache[k])
            keys.append(k)
        self.trajectories = trajs
        self.data = trajs
        self.staged, self.lane_ds = stage_partitions_dedup(
            trajs, keys, mesh=self.mesh)
        self.roots = shard_lanes(sweeps.root_keys(self._fls_pad), self.mesh)
        self.hyper = shard_lanes(sweeps.scalar_plane(self._fls_pad),
                                 self.mesh)

    def _stage_ragged(self, cfg):
        """Ragged client plane: one ``SlabStager`` per lane (deduped on the
        full plan key — a stager's host cohort draw depends on the cohort
        sizes and the fault seed, not just the dataset triple), stacked by
        ``StackedSlabStager`` into per-chunk ``(S_pad, n, K, ...)`` slabs.
        ``self.staged`` stays ``None``: there is no resident root — each
        chunk's slab is assembled (and for streaming lanes, staged) on
        demand, exactly like the single-run ragged Executor."""
        cache, lanes = {}, []
        for fl_s in self._fls_pad:
            k = (fl_s.seed, fl_s.partition, fl_s.dirichlet_alpha,
                 fl_s.n_clients, fl_s.cohort, fl_s.max_cohort,
                 fl_s.straggler_overprovision, fl_s.streaming)
            if k not in cache:
                ds = make_dataset(self.job.raw, fl_s, cfg)
                cache[k] = make_slab_stager(ds, fl_s,
                                            make_fault(self.job.raw, fl_s))
            lanes.append(cache[k])
        self.stager = StackedSlabStager(lanes)
        self.stager.set_recorder(self.recorder, self.telemetry_track)
        self.trajectories = [getattr(ln, "data", None) for ln in lanes]
        self.data = self.trajectories
        self.staged = None
        self.lane_ds = None
        self.roots = shard_lanes(sweeps.root_keys(self._fls_pad), self.mesh)
        self.hyper = shard_lanes(sweeps.scalar_plane(self._fls_pad),
                                 self.mesh)

    def _init_state(self):
        fl = self.job.fl
        self.state = shard_lanes(jax.vmap(
            lambda key: init_state(self.job.model, self.job.strategy, fl,
                                   key, n_clients_local=fl.n_clients))(
            self.roots), self.mesh)

    def _maybe_restore(self):
        """Restore onto the live mesh — elastically: a checkpoint saves
        full logical arrays with the *saving* process's padded lane dim,
        and a different ``lane_devices`` at resume means a different
        ``S_pad``. The real lanes are always the leading ``S`` rows, and
        pad lanes are frozen at their initial state (``alive = 0`` from
        launch 1) which the fresh scaffold just rebuilt bitwise — so
        reconciliation is: keep the checkpoint's first S lanes, take the
        new pad tail from the scaffolded template, then re-place on the
        mesh. Saving on 4 devices and resuming on 1 (or vice versa) is
        therefore bitwise the uninterrupted run (tests/test_shard_sweep.py
        ::test_elastic_resume_across_device_counts)."""
        if not self.ckpt_dir:
            return
        from repro.checkpoint import ckpt as ckpt_mod
        last = ckpt_mod.latest_round(self.ckpt_dir)
        if last is None:
            return
        template = self.state
        restored, extra = ckpt_mod.restore(self.ckpt_dir, last, template)
        saved_s = extra.get("campaign_lanes")
        saved_grid = extra.get("campaign_grid")
        if (saved_s is not None and saved_s != self.S) or \
                (saved_grid is not None
                 and saved_grid != self._coords_digest()):
            raise ValueError(
                f"checkpoint was written by a different sweep grid "
                f"({saved_s} lanes, digest {saved_grid}) than this one "
                f"({self.S} lanes, digest {self._coords_digest()}); a "
                "resume needs the same grid (lane_devices may differ — "
                "only the padding is elastic). Point ckpt_dir elsewhere "
                "to start the new grid fresh")

        def fit(saved, tmpl):
            if saved.shape == tmpl.shape:
                return saved
            if saved.shape[1:] != tmpl.shape[1:] or saved.shape[0] < self.S:
                raise ValueError(
                    f"checkpoint leaf {saved.shape} does not fit campaign "
                    f"state {tmpl.shape} (S={self.S}); the checkpoint was "
                    "written by an incompatible campaign, not just a "
                    "different lane_devices")
            return jnp.concatenate([saved[:self.S], tmpl[self.S:]], 0)

        self.state = shard_lanes(jax.tree.map(fit, restored, template),
                                 self.mesh)
        self.round_idx = extra["next_round"]

    def _coords_digest(self) -> str:
        """Stable digest of the expanded sweep coordinates — the identity
        of the grid, not just its size (seeds [3,5] and [11,13] both have
        S=2 but share no lane)."""
        import hashlib
        canon = repr([sorted(c.items()) for c in self.coords])
        return hashlib.sha256(canon.encode()).hexdigest()[:16]

    def _ckpt_extra(self) -> dict:
        """The real (unpadded) lane count and the grid digest ride in the
        manifest: restore rejects a checkpoint from a different sweep grid
        instead of silently adopting lanes whose coordinates belong to
        another campaign (padding alone stays elastic)."""
        return dict(super()._ckpt_extra(), campaign_lanes=self.S,
                    campaign_grid=self._coords_digest())

    def _post_restore(self):
        """Resume path: re-adopt the pre-restart rows (completed chunks are
        flushed, so a crash loses at most the open chunk) — without this a
        resumed campaign would silently write a table missing every
        pre-resume round. The append table resets so the first post-resume
        flush rewrites the (possibly crash-truncated) file consistently."""
        if self.round_idx > 0 and self.out_dir:
            prior = pathlib.Path(self.out_dir) / "campaign.csv"
            if prior.exists():
                self.results = [r for r in read_results(prior)
                                if r["round"] < self.round_idx]
        if self._table is not None:
            self._table.reset()

    def _build_schedule(self, n_rounds: int):
        """Per-trajectory virtual-clock schedules, **deduplicated**: the
        schedule is a pure function of (seed, partition, alpha — they fix
        the fault stream and the weight vector — and staleness_exponent),
        so lanes sharing that key share ONE (E,) schedule on device (the
        ROADMAP schedule-plane item; async lanes swept only over scalar
        knobs used to duplicate their schedules S times the way data used
        to). ``sched_dev`` holds the U unique schedules stacked (U, E) —
        replicated under a lane mesh — and ``lane_sched`` (S,) maps each
        lane to its row; the event program gathers the row per lane, which
        relocates identical bytes, so every lane's event stream is bitwise
        its own single staging."""
        from repro.core.async_rounds import async_init_state
        from repro.runtime.clock import build_schedule

        fl = self.job.fl
        lens = np.asarray(self.staged["len"], np.float32)   # (S_pad, C)
        cache, uniq, lane_u = {}, [], []
        for s, fl_s in enumerate(self._fls_pad):
            k = (fl_s.seed, fl_s.partition, fl_s.dirichlet_alpha,
                 fl_s.staleness_exponent)
            if k not in cache:
                cache[k] = len(uniq)
                uniq.append(build_schedule(
                    make_fault(self.job.raw, fl_s), fl.n_clients,
                    n_rounds * self.events_per_round, lens[s],
                    buffer_size=fl.async_buffer,
                    staleness_exponent=fl_s.staleness_exponent,
                    max_staleness=fl.max_staleness,
                    concurrency=fl.async_concurrency))
            lane_u.append(cache[k])
        self.schedules = [uniq[u] for u in lane_u]   # per-lane host views
        self.schedule = self.schedules[0]       # horizon checks read len()
        self.lane_sched = np.asarray(lane_u, np.int32)
        from repro.core.probes import buffer_occupancy
        occ_uniq = [buffer_occupancy(sc.accept, sc.apply) for sc in uniq]
        self._occupancy_lane = np.stack([occ_uniq[u] for u in lane_u])
        devs = [sc.device_arrays() for sc in uniq]
        sched = {k: jnp.stack([d[k] for d in devs]) for k in devs[0]}
        self.sched_dev = shard_lanes(sched, self.mesh,
                                     {k: None for k in sched})
        self._lane_sched_dev = shard_lanes(jnp.asarray(self.lane_sched),
                                           self.mesh)
        if "hist" not in self.state:
            ring = self.schedules[0].ring
            self.state = shard_lanes(jax.vmap(
                lambda st: async_init_state(st, ring, fl,
                                            self.job.strategy))(self.state),
                self.mesh)

    # -- compiled programs: the Executor's, under an outer vmap ------------
    # The concatenated roots (x, y) are NOT mapped over the sweep axis
    # (DEDUP_STAGED_AXES): one device copy serves every lane. Neither are
    # the unique (U, E) schedules — each lane gathers its row by lane_sched
    # index. Under a lane mesh the mapped inputs arrive lanes-sharded, so
    # the same jitted vmap partitions into per-device lane shards with no
    # cross-device collectives.
    def _round_program(self, n_rounds: int):
        if n_rounds not in self._programs:
            # ragged lanes carry a per-lane slab (stacked leading S_pad dim
            # on every leaf); dedup lanes share the concatenated roots and
            # map only the idx/len planes
            staged_axes = 0 if self.ragged else DEDUP_STAGED_AXES

            def launch(s, staged, roots, hyper, start, n=n_rounds):
                return jax.vmap(
                    lambda st, sg, rt, hp:
                    self._multi(self.ctx, st, sg, rt, start, n, hp),
                    in_axes=(0, staged_axes, 0, 0))(
                    s, staged, roots, hyper)
            self._programs[n_rounds] = jax.jit(launch)
        return self._programs[n_rounds]

    def _event_program(self, n_events: int):
        key = ("async", n_events)
        if key not in self._programs:
            def launch(s, staged, sched, lane_u, roots, hyper, start,
                       n=n_events):
                return jax.vmap(
                    lambda st, sg, sd, u, rt, hp:
                    self._multi(self.ctx, st, sg,
                                jax.tree.map(lambda t: t[u], sd), rt,
                                start, n, hp),
                    in_axes=(0, DEDUP_STAGED_AXES, None, 0, 0, 0))(
                    s, staged, sched, lane_u, roots, hyper)
            self._programs[key] = jax.jit(launch)
        return self._programs[key]

    # -- chunk launches (the inherited _chunk_loop drives these) ----------
    def _launch_hyper(self):
        """The scalar plane, plus — under a lane scheduler, or whenever
        device padding added dead lanes — the alive mask as a runtime
        (S_pad,) input, so drops (and the padding itself) never recompile.
        Cached between launches; a drop invalidates it."""
        if not self._thread_alive:
            return self.hyper
        if self._hyper_launch is None:
            self._hyper_launch = dict(
                self.hyper,
                alive=shard_lanes(jnp.asarray(self.alive), self.mesh))
        return self._hyper_launch

    def _skip_dead_bucket(self, n: int):
        """All lanes dropped: the compiled program would freeze every lane
        anyway, so skip the launch and emit placeholder logger rows."""
        self._tail_rows = []
        return [{"n_alive": 0, "round_s": 0.0} for _ in range(n)]

    def _launch_sync(self, start: int, n: int):
        if not self.alive_lanes():
            return self._skip_dead_bucket(n)
        t0 = time.perf_counter()
        prog = self._round_program(n)
        staged = self._stage_slab(start, n) if self.ragged else self.staged
        metrics = self._execute(
            n, prog, (self.state, staged, self.roots, self._launch_hyper(),
                      start))
        dt = time.perf_counter() - t0
        with self.recorder.span("metrics_pull", track=self.telemetry_track):
            self._capture_probes(start, n, metrics.pop("probes", None))
            cols = self._account_comms(start, n)
            stacked = {k: np.asarray(v)
                       for k, v in metrics.items()}               # (S, n)
            self._merge_comms_stacked(stacked, cols)
            return self._table_rows(stacked, start, n, dt)

    def _launch_async(self, start: int, n: int):
        if not self.alive_lanes():
            return self._skip_dead_bucket(n)
        epr = self.events_per_round
        n_ev = n * epr
        t0 = time.perf_counter()
        prog = self._event_program(n_ev)
        metrics = self._execute(
            ("async", n_ev), prog,
            (self.state, self.staged, self.sched_dev, self._lane_sched_dev,
             self.roots, self._launch_hyper(), start * epr))
        dt = time.perf_counter() - t0
        with self.recorder.span("metrics_pull", track=self.telemetry_track):
            probes = self._reduce_async_probes(metrics.pop("probes", None),
                                               n)
            ev = {k: np.asarray(v).reshape(self.S_pad, n, epr)
                  for k, v in metrics.items()}
            if probes is not None:
                from repro.core.probes import staleness_hist
                self._capture_probes(
                    start, n, probes,
                    extra=self._async_probe_extras(start, n),
                    hists={f"probe:staleness_hist:lane{s}": staleness_hist(
                        ev["staleness"][s], self.job.fl.max_staleness)
                        for s in self.alive_lanes()})
            cols = self._account_comms(start, n)
            stacked = {"loss": ev["loss"].mean(-1),
                       "staleness": ev["staleness"].mean(-1),
                       "applied": ev["applied"].sum(-1),
                       # per-lane virtual arrival time at each round
                       # window's last event (each lane reads its own
                       # schedule): async curves plot against virtual time
                       # even with comms off
                       "vtime": self._lane_vtime(start, n)}
            self._merge_comms_stacked(stacked, cols)
            return self._table_rows(stacked, start, n, dt)

    def _lane_vtime(self, start: int, n: int) -> np.ndarray:
        """(S_pad, n) virtual time at each round window's closing event."""
        epr = self.events_per_round
        idx = (start + np.arange(1, n + 1)) * epr - 1
        return np.stack([np.asarray(sc.vtime, np.float64)[idx]
                         for sc in self.schedules])

    def _async_probe_extras(self, start: int, n: int):
        """Per-lane buffer occupancy off each lane's own schedule."""
        epr = self.events_per_round
        occ = self._occupancy_lane[:, start * epr:(start + n) * epr]
        return {"buffer_occ": occ.reshape(self.S_pad, n, epr).mean(-1)}

    def _table_rows(self, stacked, start: int, n: int, dt: float):
        """Append per-(trajectory, round) rows to the tidy results table
        (alive lanes only — a dropped lane stops contributing past its drop
        round); return per-round rows (alive-lane means) for the inherited
        logger."""
        self._tail_rows = []
        live = self.alive_lanes()
        for s in live:
            for i in range(n):
                row = {**self.coords[s], "traj": s, "round": start + i,
                       **{k: float(v[s, i]) for k, v in stacked.items()},
                       "round_s": dt / n}
                self.results.append(row)
                if i == n - 1:
                    self._tail_rows.append((s, row))
        idx = np.asarray(live, np.int64)
        return [dict({k: float(v[idx, i].mean()) for k, v in stacked.items()},
                     round_s=dt / n, n_alive=len(live)) for i in range(n)]

    def _ledger_record(self, last: int):
        """One ledger block per (alive) trajectory lane: the digest of lane
        ``s`` equals the digest of the s-th single run (bitwise contract),
        so per-run provenance stays auditable — a digest of the stacked
        pytree would certify parameters no run produced."""
        for s in self.alive_lanes():
            params_s = jax.tree.map(lambda t: t[s], self.state["params"])
            self.job.ledger.record_global(last, params_s)
            self.kv.publish(f"global_digest/{last}/traj{s}",
                            param_digest(params_s))

    def _merge_eval(self, rows):
        """Per-lane eval at the chunk boundary: merged into each alive
        trajectory's tail row of the results table, means into the
        logger."""
        if self.eval_fn is None:
            return
        agg = {}
        for s, row in self._tail_rows:
            params_s = jax.tree.map(lambda t: t[s], self.state["params"])
            ev = {k: float(v) for k, v in self.eval_fn(params_s).items()}
            row.update(ev)
            for k, v in ev.items():
                agg.setdefault(k, []).append(v)
        rows[-1].update({k: float(np.mean(v)) for k, v in agg.items()})

    # -- probe plane: per-lane capture -------------------------------------
    def _capture_probes(self, start, n, probes, extra=None, hists=None):
        """Per-lane probe capture: matrices come back ``(S_pad, n)`` off the
        vmapped scan; rows land keyed like campaign.csv (coords + traj +
        round), alive lanes only — dead/padded lanes emit frozen (zero)
        probes inside the program and never reach the table."""
        if probes is None:
            return
        # one (S_pad, n, P) plane off the device, one tolist() per probe +
        # cached lane labels: the per-row work below is pure-python dict
        # building (see the base method's chunk=1 rationale)
        a = np.asarray(probes)
        cols = {name: a[..., j].tolist()
                for j, name in enumerate(PROBE_NAMES)}
        if extra:
            cols.update({k: np.asarray(v).tolist()
                         for k, v in extra.items()})
        items = sorted(cols.items())
        alive = self.alive_lanes()
        self._probe_lanes = [(s, f"lane{s}") for s in alive]
        for s in alive:
            coords = dict(self.coords[s], traj=s)
            for i in range(n):
                row = dict(coords, round=start + i)
                row.update((k, col[s][i]) for k, col in items)
                self.probe_rows.append(row)
        self._pending_probes = (start, n, cols, hists or {})

    def _probe_series(self, m, i: int) -> dict:
        """One counter series per alive lane -> per-lane Perfetto tracks."""
        return {label: m[s][i] for s, label in self._probe_lanes}

    def _probe_lead_columns(self):
        return [*self.spec.names, "traj", "round"]

    # -- comms plane: per-lane accountants ---------------------------------
    def _comms_setup(self):
        """One ``LaneComms`` accountant per (padded) lane, built from the
        lane's own expanded config + fault model — byte gating and the
        simulated clock see exactly the swept seeds/knobs the compiled
        program runs. All lanes in a bucket share the program signature, so
        one shape template (lane dim stripped; decentralized states also
        strip the per-client dim) serves every accountant."""
        if not self.comms_spec.enabled:
            return
        from repro.core.netmodel import shape_template
        tpl = shape_template(self.state["params"], strip_leading=True)
        if self.job.fl.topology == "decentralized":
            tpl = shape_template(tpl, strip_leading=True)
        self._comms = [comms_mod.LaneComms(
            fl=fl_s, csm=make_fault(self.job.raw, fl_s), template=tpl,
            pods=self.comms_spec.pods) for fl_s in self._fls_pad]

    def _account_comms(self, start: int, n: int):
        """Advance every lane's accountant: alive lanes account their
        rounds (async lanes off their own deduped schedule), dead/padded
        lanes emit frozen columns — mirroring ``freeze_unless`` so a
        dropped lane's cumulative bytes hold at the drop round. Rows land
        keyed like campaign.csv (coords + traj + round), alive lanes
        only."""
        if self._comms is None:
            return None
        per = []
        for s, lane in enumerate(self._comms):
            if self.alive[s] > 0:
                if self.mode == "async":
                    per.append(lane.async_rounds(start, n,
                                                 self.schedules[s],
                                                 self.events_per_round))
                else:
                    per.append(lane.sync_rounds(start, n))
            else:
                per.append(lane.frozen(n))
        cols = {k: np.stack([p[k] for p in per]) for k in per[0]}
        items = sorted(cols.items())
        alive = self.alive_lanes()
        self._comms_lanes = [(s, f"lane{s}") for s in alive]
        for s in alive:
            coords = dict(self.coords[s], traj=s)
            for i in range(n):
                row = dict(coords, round=start + i)
                row.update((k, float(col[s][i])) for k, col in items)
                self.comms_rows.append(row)
        self._pending_comms = (start, n, cols)
        return cols

    def _merge_comms_stacked(self, stacked: dict, cols):
        """Join the (S_pad, n) simulated-time / cumulative-byte planes into
        the stacked metrics — ``_table_rows`` then lands them per (lane,
        round) in the results table (the time-to-accuracy / bytes-to-
        accuracy x-axes) and as alive-lane means in the logger rows."""
        if cols:
            stacked.update({k: cols[k] for k in comms_mod.RESULT_COLUMNS})

    def _comms_series(self, m, i: int) -> dict:
        """One counter series per alive lane -> per-lane Perfetto tracks
        (``compression: [none, int8]`` sweeps render side by side)."""
        return {label: float(m[s][i]) for s, label in self._comms_lanes}

    def _comms_summaries(self) -> list:
        """Run-level ``comms_total`` payloads, one per real lane."""
        if self._comms is None:
            return []
        return [dict(self._comms[s].summary(), lane=s)
                for s in range(self.S)]

    def _comms_lead_columns(self):
        return [*self.spec.names, "traj", "round"]

    def _digest_record(self, event_mark: int, last: int):
        """Async digest cadence, per alive trajectory lane (same reasoning
        as ``_ledger_record``: digests must certify per-run params). Each
        block carries its lane's virtual arrival time at the event mark."""
        for s in self.alive_lanes():
            params_s = jax.tree.map(lambda t: t[s], self.state["params"])
            self._digest_blocks += 1
            self.job.ledger.append(
                last, "async_digest",
                {"event": int(event_mark), "traj": s,
                 "vtime": float(self.schedules[s].vtime[event_mark - 1]),
                 "digest": param_digest(params_s)})

    # -- flight-recorder hooks ---------------------------------------------
    def _telemetry_attrs(self) -> dict:
        """Launch-span attrs: lane occupancy at launch time (the padded
        width is what the compiled program actually scans)."""
        return {"n_alive": len(self.alive_lanes()), "S": self.S,
                "S_pad": self.S_pad}

    def _record_lane_telemetry(self):
        """Post-launch counter: alive/total lanes, plus per-shard alive
        counts under a lane mesh (lanes shard in contiguous blocks of
        ``S_pad // lane_devices`` — the shard with dead lanes is the one
        idling its device). Emitted only when occupancy changed (first
        launch, then per scheduler drop) — a steady campaign pays
        nothing per chunk for it."""
        values = {"alive": len(self.alive_lanes()), "total": self.S}
        if self.lane_devices:
            per = self.S_pad // self.lane_devices
            for d in range(self.lane_devices):
                values[f"shard{d}_alive"] = int(
                    (self.alive[d * per:(d + 1) * per] > 0).sum())
        if values != getattr(self, "_last_occupancy", None):
            self._last_occupancy = values
            self.recorder.counter("lane_occupancy",
                                  track=self.telemetry_track, **values)

    # -- results table -----------------------------------------------------
    def _lead_columns(self):
        return [*self.spec.names, "traj", "round"]

    def _finish_chunk(self, start: int, n: int, rows):
        super()._finish_chunk(start, n, rows)
        # append this chunk's rows: a crash loses at most the open chunk,
        # and resume re-adopts what is there
        if self._table is not None:
            with self.recorder.span("table_flush",
                                    track=self.telemetry_track):
                self._table.flush(self.results, self._lead_columns())

    def run(self, rounds: Optional[int] = None):
        state, logger = super().run(rounds)
        if self.out_dir:
            self._table.flush(self.results, self._lead_columns())
            if self.parquet:
                write_parquet(self.results, self._lead_columns(),
                              self.out_dir)
        return state, logger

    def trajectory_params(self, s: int):
        """Lane ``s``'s params (bitwise the s-th single run's; frozen at
        the drop round for scheduler-dropped lanes)."""
        return jax.tree.map(lambda t: np.asarray(t[s]),
                            self.state["params"])

    def write_results(self, out_dir=None):
        """Write the tidy results table in full: ``campaign.csv`` (always)
        and ``campaign.parquet`` (when pandas+pyarrow are importable).
        Schema: one row per (trajectory, round) — sweep coordinate columns
        in axis order, then ``traj``, ``round``, metric columns. The chunk
        loop appends incrementally instead (AppendTable); this is the
        explicit full-export entry point."""
        out = pathlib.Path(out_dir or self.out_dir or ".")
        out.mkdir(parents=True, exist_ok=True)
        table = (self._table if self._table is not None
                 and out == pathlib.Path(self.out_dir or ".")
                 else AppendTable(out / "campaign.csv"))
        table.reset()                  # force a consistent full rewrite
        csv_path = table.flush(self.results, self._lead_columns())
        write_parquet(self.results, self._lead_columns(), out)
        return csv_path
