"""Host-level FL executor — the faithful rendering of paper Algorithm 1.

The Logic Controller's ProcessPhase x NodeStage machine survives here as the
*host* chunk loop: everything that is genuinely I/O (checkpoint/restart,
ledger records, eval, dashboards). Everything that used to be per-round host
work — batch staging, cohort selection, straggler deadlines — now runs
*inside* the compiled program: ``core/rounds.build_multi_round`` scans
``fl.rounds_per_launch`` rounds per launch over partition tensors staged on
device once in ``scaffold()``, so the host only wakes up at chunk
boundaries. ``rounds_per_launch=1`` recovers the per-round host loop, and by
the driver's determinism contract both chunkings produce bitwise-identical
params for the same seed.

``fl.placement`` selects the client placement: "spatial" (clients vmapped
across the grid, the seed default) or "temporal" (one client at a time uses
the whole mesh); "auto" resolves to spatial.

``fl.mode`` selects the execution mode: "sync" (round-synchronous, above) or
"async" (event-driven FedAsync/FedBuff over the virtual clock — see
core/async_rounds.py). The async path shares this chunk loop shape: a
"round" is ``events_per_round`` server events, ``rounds_per_launch`` rounds
compile into one event scan, and checkpoint/ledger/eval/logging reuse the
same chunk-boundary plumbing.

ProcessPhase: 0=init 1=local-learning 2=aggregation (paper §2.3).
NodeStage:    0=not-ready 1=ready-for-job 2=ready-with-dataset
              3=busy 4=waiting/complete.
"""
from __future__ import annotations

import dataclasses
import pathlib
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt as ckpt_mod
from repro.configs.base import SWEEPABLE_SCALARS
from repro.core import determinism
from repro.core.blockchain import param_digest
from repro.core.kvstore import KVStore
from repro.core.plan import resolve_placement
from repro.core.probes import (ASYNC_REDUCE, PROBE_NAMES, ProbeSpec,
                               ProbeTable, buffer_occupancy, staleness_hist)
from repro.core.rounds import build_multi_round, build_ragged_multi, init_state
from repro.data.pipeline import make_slab_stager, slab_nbytes, stage_partitions
from repro.kernels import ops as kernel_ops
from repro.metrics.logger import PerformanceLogger, host_usage
from repro.sharding.axes import AxisCtx
from repro.telemetry import comms as comms_mod
from repro.telemetry.recorder import FlightRecorder


@dataclasses.dataclass
class Executor:
    job: Any                              # core.jobs.Job
    ctx: AxisCtx = AxisCtx()
    ckpt_dir: Optional[str] = None
    logger: Optional[PerformanceLogger] = None
    eval_fn: Optional[Callable] = None    # (params) -> dict of metrics
    # Flight recorder (repro/telemetry): host-side span tracing + launch
    # counters over the chunk-boundary seams. None -> built from the job's
    # ``telemetry:`` section (a no-op recorder when the section is absent);
    # the planner passes one shared recorder with per-bucket tracks.
    recorder: Optional[FlightRecorder] = None
    telemetry_track: str = "run"

    def __post_init__(self):
        self.kv = KVStore()
        self.logger = self.logger or PerformanceLogger(run_name=self.job.name)
        if self.recorder is None:
            self.recorder = FlightRecorder.from_job(
                self.job, fallback_dir=getattr(self, "out_dir", None))
        self._launches = 0                # launch ordinal (profile_chunks)
        # Round probe plane (core/probes.py): a ``probes:`` job section
        # compiles read-only per-round diagnostics into the scans; drained
        # at chunk boundaries into counter tracks + probes.csv.
        self.probes_spec = ProbeSpec.from_job(self.job)
        self.probe_rows = []              # tidy per-round probe rows
        self._probe_flushed = 0
        self._probe_table = None
        self._pending_probes = None       # launch stash for the drain
        # Comms observatory (telemetry/comms.py): a ``comms:`` job section
        # turns on host-side wire-traffic accounting + the simulated
        # wall-clock; accountants are built at scaffold (they need the
        # param template). Pure host bookkeeping — bitwise comms on == off.
        self.comms_spec = comms_mod.CommsSpec.from_job(self.job)
        self.comms_rows = []              # tidy per-round comms rows
        self._comms = None                # per-lane LaneComms accountants
        self._comms_flushed = 0
        self._comms_table = None
        self._pending_comms = None        # launch stash for the drain
        self._digest_blocks = 0           # async ledger-digest cadence
        # per-program FLOPs/bytes off the lowered computation (telemetry
        # report's program table); ``cost_analysis: false`` opts out
        t = (getattr(self.job, "raw", None) or {}).get("telemetry") or {}
        self._cost_enabled = bool(t.get("cost_analysis", True))
        self._cost_seen = set()
        self._last_program = None
        fl = self.job.fl
        from repro.core.jobs import validate_cohort
        validate_cohort(fl)
        # single source of truth with core/plan.py's program signatures:
        # a drift here would bucket lanes whose compiled programs differ
        self.placement = resolve_placement(fl)
        self.mode = fl.mode
        # ragged client plane (fl.max_cohort > 0): launches consume per-chunk
        # cohort slabs from a data/pipeline stager instead of a resident
        # root — n_clients/cohort never reach the trace
        self.ragged = fl.max_cohort > 0
        self.stager = None
        if self.mode == "async":
            from repro.core.async_rounds import build_async_multi
            # async "round" = events_per_round server events: one FedBuff
            # buffer flush, or (FedAsync) one arrival per client on average.
            self.events_per_round = (fl.async_buffer if fl.async_buffer > 1
                                     else fl.n_clients)
            self._multi = build_async_multi(
                self.job.model, self.job.strategy, fl,
                probes=self.probes_spec.enabled,
                on_divergence=self.probes_spec.on_divergence,
                ragged=self.ragged)
        elif self.mode == "sync":
            if self.ragged:
                self._multi = build_ragged_multi(
                    self.job.model, self.job.strategy, fl,
                    placement=self.placement,
                    probes=self.probes_spec.enabled,
                    on_divergence=self.probes_spec.on_divergence)
            else:
                self._multi = build_multi_round(
                    self.job.model, self.job.strategy, fl,
                    cfg=getattr(self.job.model, "cfg", None),
                    placement=self.placement, fault=self.job.fault,
                    probes=self.probes_spec.enabled,
                    on_divergence=self.probes_spec.on_divergence)
        else:
            raise ValueError(f"unknown mode {self.mode!r} "
                             "(want 'sync' or 'async')")
        self._programs = {}               # scan length -> jitted program
        # Sweepable scalars are threaded into the compiled programs as
        # *runtime* values even for a single run: XLA compiles a scalar-
        # multiply chain differently for a compile-time constant than for a
        # runtime value, so this is what makes a campaign lane (where the
        # scalars are vmapped (S,) arrays) bitwise-identical to this
        # single-run path (threefry + elementwise math are vmap-invariant).
        fl = self.job.fl
        self.hyper = {"seed": jnp.int32(fl.seed)}
        self.hyper.update({k: jnp.float32(getattr(fl, k))
                           for k in SWEEPABLE_SCALARS if k != "seed"})

    def compiled_programs(self) -> int:
        """How many distinct XLA programs this executor has compiled —
        the planner's bucket-count contract ("a 24-point grid with 4
        signatures compiles 4 programs") is asserted against this. Reads
        the jit caches."""
        return sum(prog._cache_size() for prog in self._programs.values())

    def _round_program(self, n_rounds: int):
        """Jitted n_rounds-launch; at most two lengths ever compile (the
        chunk size and one remainder)."""
        if n_rounds not in self._programs:
            self._programs[n_rounds] = jax.jit(
                lambda s, staged, root, hyper, start, n=n_rounds:
                self._multi(self.ctx, s, staged, root, start, n, hyper))
        return self._programs[n_rounds]

    def _event_program(self, n_events: int):
        """Jitted async launch scanning ``n_events`` server events."""
        key = ("async", n_events)
        if key not in self._programs:
            self._programs[key] = jax.jit(
                lambda s, staged, sched, root, hyper, start, n=n_events:
                self._multi(self.ctx, s, staged, sched, root, start, n,
                            hyper))
        return self._programs[key]

    def _build_schedule(self, n_rounds: int):
        """Precompute + stage the virtual-clock event schedule (async)."""
        import numpy as _np

        from repro.core.async_rounds import async_init_state
        from repro.runtime.clock import ClientSystemModel, build_schedule

        fl = self.job.fl
        csm = self.job.fault
        if not isinstance(csm, ClientSystemModel):
            csm = ClientSystemModel(**dataclasses.asdict(csm))
        lens = (_np.asarray(self.stager.lens, _np.float32) if self.ragged
                else _np.asarray(self.staged["len"], _np.float32))
        self.schedule = build_schedule(
            csm, fl.n_clients, n_rounds * self.events_per_round,
            lens,
            buffer_size=fl.async_buffer,
            staleness_exponent=fl.staleness_exponent,
            max_staleness=fl.max_staleness,
            concurrency=fl.async_concurrency)
        self.sched_dev = self.schedule.device_arrays()
        # buffer-occupancy probe stream: a pure function of the schedule's
        # accept/apply bits, so it is precomputed host-side once
        self._occupancy = buffer_occupancy(self.schedule.accept,
                                           self.schedule.apply)
        if "hist" not in self.state:
            self.state = async_init_state(self.state, self.schedule.ring,
                                          fl, self.job.strategy)

    # -- Alg. 1 lines 1-15: scaffold ------------------------------------
    def scaffold(self):
        """One scaffold sequence for single runs and campaigns; the
        campaign overrides only the staging/init/restore hooks. Each hook
        runs under a flight-recorder span (stage/init/schedule/restore are
        exactly the wall-clock sinks the report attributes outside the
        launch loop)."""
        fl = self.job.fl
        rec, track = self.recorder, self.telemetry_track
        with rec.span("scaffold", track=track):
            self.kv.set_process_phase(0)
            self.nodes = [f"client_{i}" for i in range(fl.n_clients)]
            for n in self.nodes:             # "DownloadJobConfig <- True"
                self.kv.set_node_stage(n, 1)
            with rec.span("stage_data", track=track):
                self._stage_data()
            for n in self.nodes:
                self.kv.set_node_stage(n, 2)
            with rec.span("init_state", track=track):
                self._init_state()
            if self.mode == "async":
                with rec.span("build_schedule", track=track):
                    self._build_schedule(fl.rounds)
            self.round_idx = 0
            with rec.span("restore", track=track):
                self._maybe_restore()
            self._post_restore()
            self._comms_setup()
            self._record_plane_bytes()
        return self

    def _comms_setup(self):
        """Build the comms accountant (campaigns override: one per lane).
        Needs the scaffolded param template; cumulative counters start at
        zero, so a checkpoint resume accounts only post-resume rounds."""
        if not self.comms_spec.enabled:
            return
        from repro.core.netmodel import shape_template
        fl = self.job.fl
        # decentralized params carry a per-client leading dim; the byte
        # model prices ONE model's exchange
        tpl = shape_template(self.state["params"],
                             strip_leading=fl.topology == "decentralized")
        self._comms = [comms_mod.LaneComms(
            fl=fl, csm=self.job.fault, template=tpl,
            pods=self.comms_spec.pods)]

    def _record_plane_bytes(self):
        """Counter: device bytes staged per plane (data idx/len + roots,
        async schedules, traced scalars). Computed from shapes/dtypes —
        nothing is pulled back from device."""
        rec = self.recorder
        if not rec.enabled:
            return

        def nbytes(tree):
            return int(sum(leaf.size * leaf.dtype.itemsize
                           for leaf in jax.tree.leaves(tree)))

        if self.ragged:
            # ragged mode stages no population up front: device_bytes is the
            # resident stager's root (0 when streaming); the per-chunk slab
            # working set lands as its own counter at every launch
            values = {"data_plane": int(self.stager.device_bytes),
                      "scalar_plane": nbytes(self.hyper)}
        else:
            values = {"data_plane": nbytes(self.staged),
                      "scalar_plane": nbytes(self.hyper)}
        if getattr(self, "sched_dev", None) is not None:
            values["schedule_plane"] = nbytes(self.sched_dev)
        rec.counter("staged_bytes", track=self.telemetry_track, **values)

    def _record_slab_bytes(self, slab):
        """Per-chunk ``staged_bytes`` counter for ragged launches: the
        slab working set this launch actually staged, the stager's running
        peak, and what full residency would have cost — the streaming
        plane's bounded-memory claim, measurable from telemetry.jsonl."""
        rec = self.recorder
        if not rec.enabled:
            return
        rec.counter("staged_bytes", track=self.telemetry_track,
                    slab=slab_nbytes(slab),
                    peak_slab=int(self.stager.peak_slab_bytes),
                    resident_equiv=int(self.stager.resident_bytes))

    def _stage_data(self):
        """"DownloadDataset": the one-time device staging of the full client
        partition — the round loop never touches host data after this.
        Ragged mode builds a slab stager instead: staging happens per chunk
        (resident gather or streaming host->device copies)."""
        fl = self.job.fl
        if self.ragged:
            self.stager = make_slab_stager(self.job.dataset, fl,
                                           self.job.fault)
            self.stager.set_recorder(self.recorder, self.telemetry_track)
            self.staged = None
            self.data = getattr(self.stager, "data", None)
            return
        x, y, parts = self.job.dataset.distribute_into_chunks(
            fl.partition, fl.n_clients, fl.dirichlet_alpha)
        self.data = (x, y, parts)   # host view, kept for eval_fn consumers
        self.staged = stage_partitions(x, y, parts)

    def _init_state(self):
        fl = self.job.fl
        # built once: the chunk loop passes it to every launch
        self.root = determinism.root_key(fl.seed)
        self.state = init_state(self.job.model, self.job.strategy, fl,
                                self.root, n_clients_local=fl.n_clients)

    def _post_restore(self):
        """Hook after a checkpoint restore (campaigns re-adopt their
        results table here)."""

    def _maybe_restore(self):
        """Restart path (fault tolerance): resume from the newest manifest."""
        if self.ckpt_dir:
            last = ckpt_mod.latest_round(self.ckpt_dir)
            if last is not None:
                self.state, extra = ckpt_mod.restore(
                    self.ckpt_dir, last, self.state)
                self.round_idx = extra["next_round"]

    # -- Alg. 1 lines 16-57: chunked round loop ---------------------------
    def run(self, rounds: Optional[int] = None):
        """Run (or continue) the chunked round loop up to ``rounds``."""
        rounds = rounds or self.job.fl.rounds
        self._run_total = rounds      # sizes the ragged stager's prefetch
        if self.mode == "async":
            self._check_async_horizon(rounds)
            return self._chunk_loop(rounds, self._launch_async)
        return self._chunk_loop(rounds, self._launch_sync)

    def _chunk_loop(self, rounds: int, launch):
        """The shared chunked round loop (sync, async, and campaign
        execution all use it): per chunk, phase bookkeeping, one compiled
        launch (``launch(start, n) -> rows``, one metrics row per round),
        then chunk-boundary host I/O (``_finish_chunk``). With telemetry
        on, the loop runs inside its own quant-agg counter scope (runs in
        one process can't bleed routing counts into each other) and the
        run-level totals land as counters at the end."""
        rec = self.recorder
        if not rec.enabled:
            return self._chunk_loop_inner(rounds, launch)
        with kernel_ops.quant_agg_scope() as qframe:
            out = self._chunk_loop_inner(rounds, launch)
        rec.counter("quant_agg", track=self.telemetry_track,
                    calls=qframe["calls"],
                    batched_fallbacks=qframe["batched_fallbacks"])
        rec.counter("programs", track=self.telemetry_track,
                    compiled=self.compiled_programs())
        for values in self._comms_summaries():
            rec.counter("comms_total", track=self.telemetry_track, **values)
        rec.flush()
        return out

    def _chunk_loop_inner(self, rounds: int, launch):
        chunk = max(self.job.fl.rounds_per_launch, 1)
        rec, track = self.recorder, self.telemetry_track
        while self.round_idx < rounds:
            start = self.round_idx
            n = min(chunk, rounds - start)
            # phase 1+2 (cohort selection, local learning, aggregation) all
            # happen inside the compiled program
            self.kv.set_process_phase(1)
            for node in self.nodes:
                self.kv.set_node_stage(node, 3)
            self.kv.set_process_phase(2)
            with rec.span("chunk", track=track, start=start, n=n):
                rows = self._recorded_launch(launch, start, n)
                with rec.span("finish_chunk", track=track):
                    self._finish_chunk(start, n, rows)
        return self.state, self.logger

    def _recorded_launch(self, launch, start: int, n: int):
        """One compiled launch under a "launch" span carrying the per-launch
        telemetry: compile-count delta (jit-cache reading — a launch that
        grew the cache is a cold/compile launch), quant-agg routing delta,
        and the driver-specific attrs (lane occupancy for campaigns); host
        RSS/CPU and lane counters sample after the launch. ``profile()``
        wraps the launch in a jax.profiler capture when the job's
        ``telemetry.profile_chunks`` lists this launch ordinal."""
        rec = self.recorder
        if not rec.enabled:
            return launch(start, n)
        ordinal = self._launches
        self._launches += 1
        progs0 = self.compiled_programs()
        calls0 = kernel_ops.quant_agg_stats()["calls"]
        with rec.profile(ordinal), \
                rec.span("launch", track=self.telemetry_track,
                         mode=self.mode, start=start, n=n,
                         ordinal=ordinal) as sp:
            rows = launch(start, n)
            sp.attrs.update(
                compile_delta=self.compiled_programs() - progs0,
                quant_agg_traces=(kernel_ops.quant_agg_stats()["calls"]
                                  - calls0),
                **self._telemetry_attrs())
        rec.counter("host", track=self.telemetry_track, **host_usage())
        self._record_lane_telemetry()
        self._record_program_cost(sp)
        self._drain_probe_counters(sp._t0, rec._now_us())
        self._drain_comms_counters(sp._t0, rec._now_us())
        return rows

    def _record_program_cost(self, sp):
        """FLOPs/bytes per compiled program off ``Lowered.cost_analysis()``
        (lowering only retraces — no second backend compile), recorded once
        per program key on its compile launch; the telemetry report's
        program table picks the counter up."""
        stash, self._last_program = self._last_program, None
        if stash is None or not sp.attrs.get("compile_delta"):
            return
        key, prog, args = stash
        if key in self._cost_seen:
            return
        self._cost_seen.add(key)
        try:
            cost = prog.lower(*args).cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            values = {
                "flops": float(cost.get("flops", 0.0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0.0))}
        except Exception:
            return                 # cost analysis is backend-best-effort
        self.recorder.counter("program_cost", track=self.telemetry_track,
                              program=str(key), **values)

    # -- probe drain (core/probes.py) -------------------------------------
    def _capture_probes(self, start: int, n: int, probes, extra=None,
                        hists=None):
        """Stash a launch's per-round probe matrices: tidy rows buffer now
        (flushed to probes.csv at the chunk boundary), counter samples at
        ``_drain_probe_counters`` (back-dated across the launch span —
        probes are device values the host first sees at the boundary)."""
        if probes is None:
            return
        # one (n, P) matrix off the device, one tolist(): everything
        # downstream (rows, counter series, json/csv encoding) works on
        # native python floats — per-element numpy scalar extraction and
        # per-probe transfers dominate at chunk=1
        a = np.asarray(probes)
        cols = {name: a[..., j].tolist()
                for j, name in enumerate(PROBE_NAMES)}
        if extra:
            cols.update({k: np.asarray(v).tolist()
                         for k, v in extra.items()})
        items = sorted(cols.items())
        for i in range(n):
            row = {"round": start + i}
            row.update((k, col[i]) for k, col in items)
            self.probe_rows.append(row)
        self._pending_probes = (start, n, cols, hists or {})

    def _drain_probe_counters(self, t0_us: int, t1_us: int):
        """Perfetto "C" tracks: one ``probe:<name>`` counter per probe (the
        campaign override emits one series per alive lane), per-round
        samples interpolated across the launch span they were computed
        inside; histogram counters land at the span end."""
        pend, self._pending_probes = self._pending_probes, None
        if pend is None or not self.recorder.enabled:
            return
        start, n, mats, hists = pend
        rec, track = self.recorder, self.telemetry_track
        for i in range(n):
            t = int(t0_us + (t1_us - t0_us) * (i + 1) / n)
            for name, m in mats.items():
                rec.counter(f"probe:{name}", track=track, t_us=t,
                            **self._probe_series(m, i))
        for name, values in hists.items():
            rec.counter(name, track=track, t_us=t1_us, **values)

    def _probe_series(self, m, i: int) -> dict:
        """Counter series for round ``i`` (campaigns: one per alive lane)."""
        return {"value": m[i]}

    def _reduce_async_probes(self, probes, n: int):
        """(..., n_events, P) per-event probe plane -> (..., n, P)
        per-round values. The reductions are fixed per probe
        (core/probes.ASYNC_REDUCE) and rounds are fixed event windows, so
        any chunking yields the same per-round stream."""
        if probes is None:
            return None
        epr = self.events_per_round
        a = np.asarray(probes)
        a = a.reshape(a.shape[:-2] + (n, epr, a.shape[-1]))
        out = np.empty(a.shape[:-3] + (n, a.shape[-1]), np.float32)
        for j, name in enumerate(PROBE_NAMES):
            red = ASYNC_REDUCE.get(name, "mean")
            out[..., j] = getattr(a[..., j], red)(axis=-1)
        return out

    def _async_probe_extras(self, start: int, n: int):
        """Host-side async probe columns: per-round mean buffer occupancy
        (precomputed from the schedule's accept/apply stream)."""
        epr = self.events_per_round
        occ = self._occupancy[start * epr:(start + n) * epr]
        return {"buffer_occ": occ.reshape(n, epr).mean(-1)}

    # -- comms drain (telemetry/comms.py) ---------------------------------
    def _account_comms(self, start: int, n: int):
        """Advance the comms accountant over this launch's rounds: tidy
        rows buffer now (flushed to comms.csv at the chunk boundary),
        counter samples at ``_drain_comms_counters``. Returns the per-round
        column dict (the launch merges ``sim_time_s``/``cum_bytes`` into
        its result rows) or None with comms off."""
        if self._comms is None:
            return None
        lane = self._comms[0]
        if self.mode == "async":
            cols = lane.async_rounds(start, n, self.schedule,
                                     self.events_per_round)
        else:
            cols = lane.sync_rounds(start, n)
        items = sorted(cols.items())
        for i in range(n):
            row = {"round": start + i}
            row.update((k, float(col[i])) for k, col in items)
            self.comms_rows.append(row)
        self._pending_comms = (start, n, cols)
        return cols

    def _merge_comms(self, rows, cols, n: int):
        """Join the simulated-time / cumulative-byte columns onto the
        launch's result rows — eval metrics merged into the same rows then
        plot directly as time-to-accuracy / bytes-to-accuracy curves."""
        if cols:
            for i in range(n):
                rows[i].update({k: float(cols[k][i])
                                for k in comms_mod.RESULT_COLUMNS})
        return rows

    def _drain_comms_counters(self, t0_us: int, t1_us: int):
        """Perfetto "C" tracks: cumulative per-direction bytes + the
        virtual-time track (campaigns: one series per alive lane),
        back-dated across the launch span like the probe counters."""
        pend, self._pending_comms = self._pending_comms, None
        if pend is None or not self.recorder.enabled:
            return
        start, n, cols = pend
        rec, track = self.recorder, self.telemetry_track
        for i in range(n):
            t = int(t0_us + (t1_us - t0_us) * (i + 1) / n)
            for name in comms_mod.COUNTER_COLUMNS:
                rec.counter(f"comms:{name}", track=track, t_us=t,
                            **self._comms_series(cols[name], i))

    def _comms_series(self, m, i: int) -> dict:
        """Counter series for round ``i`` (campaigns: one per alive lane)."""
        return {"value": float(m[i])}

    def _comms_summaries(self) -> list:
        """Run-level ``comms_total`` counter payloads (campaigns: one per
        lane, tagged with its index)."""
        if self._comms is None:
            return []
        return [self._comms[0].summary()]

    def _telemetry_attrs(self) -> dict:
        """Driver-specific launch-span attrs (campaigns: lane occupancy)."""
        return {}

    def _record_lane_telemetry(self):
        """Post-launch counters hook (campaigns: per-shard lane alive)."""

    def _prefetch_next(self, start: int, n: int) -> None:
        """Kick the stager's double buffer for the next chunk, so its host
        gather + host->device copy overlap this launch's device time."""
        chunk = max(self.job.fl.rounds_per_launch, 1)
        total = getattr(self, "_run_total", self.job.fl.rounds)
        nxt = min(chunk, total - (start + n))
        if nxt > 0:
            self.stager.prefetch(start + n, nxt)

    def _stage_slab(self, start: int, n: int):
        """The ragged chunk's slab, and the kick of the next chunk's
        prefetch, under a ``stage_slab`` span (the stager's host cohort
        plan is its ``cohort_plan`` child)."""
        with self.recorder.span("stage_slab", track=self.telemetry_track):
            staged = self.stager.slab(start, n)
            self._record_slab_bytes(staged)
            self._prefetch_next(start, n)
        return staged

    def _stage_event_slab(self, e0: int, n_ev: int):
        """The ragged event window's rows, and the kick of the next
        window's prefetch, under a ``stage_slab`` span."""
        epr, clients = self.events_per_round, self.schedule.client
        with self.recorder.span("stage_slab", track=self.telemetry_track):
            staged = self.stager.event_slab(clients[e0:e0 + n_ev],
                                            tag=(e0, n_ev))
            self._record_slab_bytes(staged)
            chunk_ev = max(self.job.fl.rounds_per_launch, 1) * epr
            total_ev = getattr(self, "_run_total", self.job.fl.rounds) * epr
            nxt = min(chunk_ev, total_ev - (e0 + n_ev))
            if nxt > 0:
                self.stager.prefetch_events(
                    clients[e0 + n_ev:e0 + n_ev + nxt], tag=(e0 + n_ev, nxt))
        return staged

    def _execute(self, key, prog, args):
        """One compiled launch: ``dispatch`` (argument transfer, enqueue,
        and the compile on a cold launch), then ``device_wait`` until the
        new state is ready. Returns the launch's device metrics."""
        rec, track = self.recorder, self.telemetry_track
        if rec.enabled and self._cost_enabled:
            self._last_program = (key, prog, args)
        with rec.span("dispatch", track=track):
            state, metrics = prog(*args)
        with rec.span("device_wait", track=track):
            self.state = jax.block_until_ready(state)
        return metrics

    def _launch_sync(self, start: int, n: int):
        t0 = time.perf_counter()
        prog = self._round_program(n)
        staged = self._stage_slab(start, n) if self.ragged else self.staged
        metrics = self._execute(
            n, prog, (self.state, staged, self.root, self.hyper, start))
        dt = time.perf_counter() - t0
        with self.recorder.span("metrics_pull", track=self.telemetry_track):
            self._capture_probes(start, n, metrics.pop("probes", None))
            cols = self._account_comms(start, n)
            stacked = {k: np.asarray(v) for k, v in metrics.items()}
            return self._merge_comms(
                [dict({k: float(v[i]) for k, v in stacked.items()},
                      round_s=dt / n) for i in range(n)], cols, n)

    def _launch_async(self, start: int, n: int):
        """An async "round" is ``events_per_round`` server events; only the
        compiled program differs from the sync launch (an event scan
        instead of a round scan)."""
        epr = self.events_per_round
        n_ev = n * epr
        t0 = time.perf_counter()
        prog = self._event_program(n_ev)
        e0 = start * epr
        staged = (self._stage_event_slab(e0, n_ev) if self.ragged
                  else self.staged)
        metrics = self._execute(
            ("async", n_ev), prog,
            (self.state, staged, self.sched_dev, self.root, self.hyper, e0))
        dt = time.perf_counter() - t0
        with self.recorder.span("metrics_pull", track=self.telemetry_track):
            probes = self._reduce_async_probes(metrics.pop("probes", None),
                                               n)
            stacked = {k: np.asarray(v).reshape(n, epr)
                       for k, v in metrics.items()}
            if probes is not None:
                self._capture_probes(
                    start, n, probes,
                    extra=self._async_probe_extras(start, n),
                    hists={"probe:staleness_hist": staleness_hist(
                        stacked["staleness"], self.job.fl.max_staleness)})
            cols = self._account_comms(start, n)
            # virtual arrival time at each round window's last event: async
            # curves plot against virtual time even with comms accounting
            # off
            vt = self.schedule.vtime
            return self._merge_comms(
                [{"loss": float(stacked["loss"][i].mean()),
                  "staleness": float(stacked["staleness"][i].mean()),
                  "applied": float(stacked["applied"][i].sum()),
                  "vtime": float(vt[(start + i + 1) * epr - 1]),
                  "round_s": dt / n,
                  "events_per_s": n_ev / max(dt, 1e-9)}
                 for i in range(n)], cols, n)

    def _check_async_horizon(self, rounds: int):
        """Horizon grew past the scaffolded schedule? Regenerating is only
        safe before any event ran (or for FedAsync, which has no buffer
        groups): a FedBuff group left open at the old horizon gets
        renormalized coefficients once the longer horizon closes it, which
        would silently de-normalize contributions already folded into the
        carried accumulator."""
        fl = self.job.fl
        epr = self.events_per_round
        if rounds * epr > len(self.schedule):
            if self.round_idx > 0 and fl.async_buffer > 1:
                raise RuntimeError(
                    f"async run asked for {rounds} rounds mid-flight but "
                    f"the schedule covers {len(self.schedule) // epr}; "
                    "scaffold with a larger fl.rounds (or resume from a "
                    "checkpoint) instead of growing a FedBuff run in place")
            self._build_schedule(rounds)

    def _finish_chunk(self, start: int, n: int, rows):
        """Chunk-boundary host I/O, shared by the sync/async/campaign loops:
        ledger record, eval (merged into the last round's row), logging,
        round-index advance, checkpoint-cadence save."""
        fl = self.job.fl
        rec, track = self.recorder, self.telemetry_track
        for node in self.nodes:
            self.kv.set_node_stage(node, 4)
        last = start + n - 1
        if self.job.ledger is not None:
            with rec.span("ledger", track=track):
                self._ledger_record(last)
        if self.eval_fn is not None:
            with rec.span("eval", track=track):
                self._merge_eval(rows)
        else:
            self._merge_eval(rows)
        for i in range(n):
            self.logger.log_round(start + i, **rows[i])
        if self.probes_spec.enabled and \
                len(self.probe_rows) > self._probe_flushed:
            with rec.span("probe_flush", track=track):
                self._flush_probes()
        if self.comms_spec.enabled and \
                len(self.comms_rows) > self._comms_flushed:
            with rec.span("comms_flush", track=track):
                self._flush_comms()
        if self.mode == "async" and fl.digest_every_events > 0 and \
                self.job.ledger is not None:
            self._digest_cadence(start, n, last)
        self.round_idx += n
        # save when this chunk crossed a checkpoint_every multiple (the
        # cadence survives chunk sizes that don't divide it)
        if self.ckpt_dir and fl.checkpoint_every and \
                start // fl.checkpoint_every != \
                self.round_idx // fl.checkpoint_every:
            with rec.span("checkpoint_save", track=track,
                          round=self.round_idx):
                ckpt_mod.save(self.ckpt_dir, self.round_idx, self.state,
                              extra=self._ckpt_extra(), async_write=False)

    def _ckpt_extra(self) -> dict:
        """Checkpoint manifest extras (campaigns add the lane count so a
        resume against a different sweep grid fails loudly)."""
        return {"next_round": self.round_idx}

    # -- probes.csv --------------------------------------------------------
    def _probe_lead_columns(self):
        return ["round"]

    def _probe_path(self) -> Optional[pathlib.Path]:
        """Where probes.csv lands: the ``probes.out_dir`` knob, else the
        telemetry out_dir, else the executor's own out_dir/ckpt_dir (rows
        stay memory-only when none is set). Non-default tracks (planner
        buckets) suffix the filename so a shared dir cannot collide."""
        spec = self.probes_spec
        out = spec.out_dir or \
            (self.recorder.out_dir if self.recorder.enabled else None) or \
            getattr(self, "out_dir", None) or self.ckpt_dir
        if out is None:
            return None
        name = ("probes.csv" if self.telemetry_track == "run"
                else f"probes_{self.telemetry_track}.csv")
        return pathlib.Path(out) / name

    def _flush_probes(self):
        """Append the rows buffered since the last boundary to probes.csv
        (tidy, keyed like campaign.csv); ``self.probe_rows`` keeps the full
        in-memory view either way."""
        new = self.probe_rows[self._probe_flushed:]
        self._probe_flushed = len(self.probe_rows)
        if not new:
            return
        if self._probe_table is None:
            path = self._probe_path()
            if path is None:
                return
            self._probe_table = ProbeTable(path, self._probe_lead_columns())
        self._probe_table.flush(new)

    # -- comms.csv ---------------------------------------------------------
    def _comms_lead_columns(self):
        return ["round"]

    def _comms_path(self) -> Optional[pathlib.Path]:
        """Where comms.csv lands: the ``comms.out_dir`` knob, else the
        telemetry out_dir, else the executor's own out_dir/ckpt_dir (rows
        stay memory-only when none is set); planner buckets suffix the
        track like probes.csv."""
        out = self.comms_spec.out_dir or \
            (self.recorder.out_dir if self.recorder.enabled else None) or \
            getattr(self, "out_dir", None) or self.ckpt_dir
        if out is None:
            return None
        name = ("comms.csv" if self.telemetry_track == "run"
                else f"comms_{self.telemetry_track}.csv")
        return pathlib.Path(out) / name

    def _flush_comms(self):
        """Append the rows buffered since the last boundary to comms.csv
        (tidy, keyed like campaign.csv); ``self.comms_rows`` keeps the full
        in-memory view either way. The column set is fixed
        (netmodel.COMMS_COLUMNS), so ProbeTable's append-only writer fits."""
        new = self.comms_rows[self._comms_flushed:]
        self._comms_flushed = len(self.comms_rows)
        if not new:
            return
        if self._comms_table is None:
            path = self._comms_path()
            if path is None:
                return
            self._comms_table = ProbeTable(path, self._comms_lead_columns())
        self._comms_table.flush(new)

    # -- async ledger-digest cadence (ROADMAP carried item) ----------------
    def _digest_cadence(self, start: int, n: int, last: int):
        """Emit one ledger digest block per ``digest_every_events`` mark the
        finished chunk crossed (evaluated at chunk boundaries — the block
        digests the boundary state, so the block *count* is chunking-
        invariant). Recorded as a "digest" span + cumulative counter so
        digest cost shows in the telemetry report."""
        rec, track = self.recorder, self.telemetry_track
        epr = self.events_per_round
        d = self.job.fl.digest_every_events
        e0, e1 = start * epr, (start + n) * epr
        marks = range((e0 // d + 1) * d, e1 + 1, d)
        if not len(marks):
            return
        with rec.span("digest", track=track, events=e1, blocks=len(marks)):
            for m in marks:
                self._digest_record(m, last)
        rec.counter("digest", track=track, blocks=self._digest_blocks)

    def _digest_record(self, event_mark: int, last: int):
        """One digest block (campaigns override: one per alive lane). The
        block carries the virtual arrival time of its event mark, so ledger
        rows line up with the async virtual-time axis."""
        self._digest_blocks += 1
        self.job.ledger.append(
            last, "async_digest",
            {"event": int(event_mark),
             "vtime": float(self.schedule.vtime[event_mark - 1]),
             "digest": param_digest(self.state["params"])})

    def _ledger_record(self, last: int):
        """Ledger hook at the chunk boundary (campaigns override: one block
        per trajectory lane, so per-run provenance stays auditable)."""
        dig = param_digest(self.state["params"])
        self.job.ledger.record_global(last, self.state["params"])
        self.kv.publish(f"global_digest/{last}", dig)

    def _merge_eval(self, rows):
        """Eval hook at the chunk boundary (campaigns override: per-lane)."""
        if self.eval_fn is not None:
            rows[-1].update({k: float(v) for k, v in
                             self.eval_fn(self.state["params"]).items()})
