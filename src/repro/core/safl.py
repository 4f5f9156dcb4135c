"""SFL / SAFL engines (paper §2.2, Fig. 1) — discrete-event simulation.

Only *simulated* wall-clock (lognormal per-client compute speeds +
communication latency) is event-driven; host compute batches to the
schedule's dependency structure.  Simulated time orders the events; it
does not defer any computation.

Synchronous (SFL, Fig. 1a): each round the server activates K random
clients, waits for all of them (round time = slowest active client — the
straggler effect), aggregates, broadcasts.  The K same-shape clients run as
ONE vmapped XLA program (client.make_batched_local_train) that emits the
raveled (K, D) update buffer directly — with or without the quantized
channel.

Semi-asynchronous (SAFL, Fig. 1b): clients train continuously at their own
pace and upload after each local epoch; the server aggregates as soon as K
updates are buffered and broadcasts; a client adopts the newest global model
at its next upload boundary, otherwise continues training its local one —
so buffered updates carry staleness τ = t_now − t_client_version.

*Horizon-batched execution* (``batch_clients=True``, the default): between
two aggregation boundaries the K buffered uploads depend only on state
fixed at the previous boundary — each client's first upload of the horizon
trains from its own carried weights, and every later upload of the same
client trains from the freshly adopted global model or its own local chain.
The engine therefore pops the event heap to the next aggregation horizon
up front, groups the K events into *waves* (event #j of a client within
the horizon is wave j; in steady state almost everything is wave 0), and
runs each wave as ONE vmapped XLA program over heterogeneous per-client
parameters (client.make_batched_hetero_train).  Clients carry their
weights as flat (D,) rows (flatbuf.PytreeCodec layout), so stacking a wave
is one device concat, the wave program emits the (K, D) update rows
directly into the aggregation buffer (one scatter per wave), and the
global model stays flat end-to-end — it is unraveled to a pytree exactly
once, when the run finishes.  No ``float()`` host sync survives in the
hot loop: per-upload losses are never fetched, eval is an
``eval_every``-gated jitted call, and eval/update-norm scalars land in a
device-resident metrics ring (metrics.DeviceMetricsRing) flushed once at
run end.  ``batch_clients=False`` forces the sequential per-upload path —
the parity oracle for the batched schedule.

Lossy wire formats (``FLConfig.wire`` — q8 / q4 / topk;
``compress_updates=True`` is the legacy q8 alias): the wire payload is
the native buffer format, not a detour through f32.  A gradient-target
upload is ONE fused program (``PytreeCodec.ravel_delta_q8`` /
``ravel_delta_q4`` / ``ravel_delta_topk``: diff + ravel + EF add +
quantize/sparsify) that also returns the client-side error-feedback
residual — what the wire dropped this round is re-added to the next
upload, so the noise telescopes instead of accumulating.  q4 rounds
stochastically with draws keyed per (client, upload counter) — see
``_next_counter`` — so the sequential and batched paths quantize
bit-identically.  Model-target uploads quantize the weights themselves
(``ravel_q8`` / ``ravel_q4_nores``, no residual; topk is
gradient-only).  The rows live in a donated
:class:`repro.core.flatbuf.QuantBuffer` (int8 values or packed int4
nibble pairs + per-block f32 scales) or
:class:`repro.core.flatbuf.TopkBuffer` (sparse index/value/scale
triple), batched waves quantize all their rows in one vmapped program
(``quantize_rows*``), and the server round fuses the dequantize — for
topk, a gather-dequant-scatter-accumulate that never builds a dense
(K, D) buffer — into the aggregation pass.

The server round itself is ONE jitted program
(:class:`repro.core.aggregation.FlatServer` — fused [dequantize +]
staleness discount + weighted reduction + server step + update-norm metric,
Pallas-backed on TPU) for EVERY aggregation mode: fedsgd / fedavg /
fedbuff / fedopt / sdga as buffered reductions, and fedasync's K
sequential per-update mixes folded into one linear combination
(aggregation.fedasync_coefficients + the kernels' ``mix`` mode) — the
per-leaf pytree aggregation path is fully retired.

*Multi-device execution* (``devices > 1`` or ``mesh_shape=(E, P)``): the
flat (K, D) channel — f32 buffer or int8
:class:`repro.core.flatbuf.QuantBuffer` — lives row-sharded over the mesh
row axes (:mod:`repro.sharding.flat`): a 1-D "pod" axis under
``devices``, or the *flattened* 2-D (edge, pod) axis under
``mesh_shape`` — the hierarchical clients -> edge aggregators -> server
topology.  The batched wave programs pin their client lanes to the same
axes with in-program sharding constraints (wave training runs
data-parallel across devices and scatters already-sharded rows), and the
server round lowers to per-shard partial weighted sums (the kernels'
``mode="sum"`` grid / streaming-q8 reference) folded by the mesh-shaped
collective (sharding.flat.podwise_sums) before the replicated server
step: ONE global psum on the 1-D mesh; log2(P) intra-edge ppermute
tree-reduce rounds + ONE cross-edge psum of E edge partials on the 2-D
mesh (cross-edge traffic shrinks ~P x — FlatServer.traffic holds the
measured bytes).  ``mesh_shape=(1, P)`` is the bit-exact ``devices=P``
alias.

*Wave compilation policy*: each distinct wave size is a distinct XLA
program (K is a static shape), so ``wave_buckets`` pads waves to the next
power of two with masked lanes — padding lanes duplicate a real lane's
inputs and scatter to slot K, which the drop-mode write discards — so
high-churn schedules compile O(log K) programs instead of one per distinct
size.  ``wave_impl`` selects vmap (vectorized lanes) or ``lax.map``
(serial lanes, one dispatch — same numerics, no grouped-convolution
lowering penalty for conv models on CPU); ``"auto"`` picks per model and
backend (client.resolve_wave_impl).

*Client scheduling* (:mod:`repro.sched`): simulated time and
participation are pluggable.  A ``Scheduler`` built from the
``FLConfig.sched_*`` knobs owns the persistent event heap, the
device-time model (static / lognormal jitter / Markov availability) and
the participation policy (full / uniform C-of-N / SEAFL staleness-capped
selective training / FedQS adaptive reweighting); both SAFL paths
consume its upload-decision stream, so the sequential and
horizon-batched schedules stay identical under every model x policy, and
``sched_policy="full"`` + ``sched_timing="static"`` reproduce the
pre-sched engine bit-exactly.  Rejected uploads (selective policies)
discard the client's local progress and resync it to the current global
model — in the batched path that training never runs at all, which is
the point of selective training.  Adaptive policies hand re-scored
aggregation coefficients to a ``FlatServer(external_discount=True)``.
Per-client participation counts and a device-resident staleness
histogram ride the metrics ring (one extra host transfer per run) into
``FLResult.participation`` / ``FLResult.sched_stats``.
"""
from __future__ import annotations

import dataclasses
import time as _walltime
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro import faults as faultsmod
from repro import sched as schedmod
from repro.checkpoint import io as ckptio
from repro.core import aggregation as agg
from repro.core import flatbuf
from repro.core.client import (ClientState, make_batched_hetero_train,
                               make_batched_local_train, make_eval_fn,
                               make_flat_eval_fn, make_local_train,
                               pytree_bytes, resolve_wave_impl,
                               broadcast_states, split_states,
                               stack_states, take_states)
from repro.core.metrics import DeviceMetricsRing, MetricsLog, RoundRecord
from repro.kernels.quantize import payload_nbytes
from repro.sharding import flat as shflat

Pytree = Any

# device-resident staleness histogram width (last bin = overflow); the
# host-side dict in FLResult.staleness_hist stays unbounded
_STALE_BINS = 32

# simulated samples/second at speed 1.0
_BASE_RATE = 500.0
# serialization envelope: full-model upload (FedAvg) carries the layer
# structure; gradient upload (FedSGD) is a bare tensor list (paper §5.1.2)
_MODEL_ENVELOPE = 0.010
_GRAD_ENVELOPE = 0.002

# aggregation targets that upload model weights (vs cumulative gradients)
_MODEL_TARGETS = ("fedavg", "fedasync")

# wall-clock stage span in the jax.profiler trace (host plane, the device
# planes' clock); costs about a microsecond when no profiler runs.  The
# span names and their stats: repro/obs/README.md, "Stage spans"
_span = jax.profiler.TraceAnnotation


def _floats(report) -> Dict[str, float]:
    """The counters a model reported from an evaluation, as floats."""
    return {k: float(v) for k, v in report.items()}


@dataclasses.dataclass
class FLResult:
    metrics: MetricsLog
    final_params: Pytree
    staleness_hist: Dict[int, int]
    idle_time: float  # SFL: total simulated idle seconds across clients
    # per-client admitted-upload counts (host accounting, both paths) +
    # the scheduler summary: policy/timing names, rejected-upload and
    # no-show totals, and — batched path — the device-resident staleness
    # histogram accumulated in the DeviceMetricsRing (one transfer per
    # run; "staleness_bins" key, last bin = overflow)
    participation: Optional[np.ndarray] = None
    sched_stats: Optional[Dict] = None


class FLEngine:
    """One experiment = FLEngine(...).run(n_rounds)."""

    def __init__(self, fl_cfg, apply_fn: Callable, kind: str,
                 init_params: Pytree, init_state: Pytree,
                 client_shards: Sequence[Dict[str, np.ndarray]],
                 test_x: np.ndarray, test_y: np.ndarray,
                 frozen: Optional[Pytree] = None):
        """``frozen``: a part of the model every client reads and none
        trains (a base model under LoRA adapters, ``apply_fn(...,
        frozen=)``).  It is held once on the device and handed to the
        client, wave and evaluation programs as an argument; it is never
        uploaded, aggregated, snapshotted or copied per client: only
        ``init_params`` are."""
        fl_cfg.validate()
        self.cfg = fl_cfg
        self.kind = kind
        self.apply_fn = apply_fn
        self.epoch_fn = make_local_train(apply_fn, kind)
        self.eval_fn = make_eval_fn(apply_fn, kind)
        self.test_x, self.test_y = jnp.asarray(test_x), jnp.asarray(test_y)

        rng = np.random.default_rng(fl_cfg.seed)
        self.clients: List[ClientState] = []
        for cid, shard in enumerate(client_shards):
            speed = float(np.exp(rng.normal(0.0, fl_cfg.speed_sigma)))
            comm = float(fl_cfg.comm_mean_s
                         * np.exp(rng.normal(0.0, 0.3)))
            self.clients.append(ClientState(
                cid=cid, params=init_params, model_state=init_state,
                version=0, n_samples=int(shard["n"]), speed=speed,
                comm_time=comm, rng=np.random.default_rng(
                    fl_cfg.seed * 7919 + cid)))
        self.shards = client_shards
        self.global_params = init_params
        self.global_state = init_state
        self.t_global = 0
        self.rng = rng

        # ---- scheduling subsystem: simulated time + participation ----
        # (repro.sched: device-time model, participation policy and the
        # persistent event heap — replaces the engine's inlined heap)
        self.sched = schedmod.build_scheduler(fl_cfg, self.clients,
                                              self._base_compute)
        # device-resident sched-stat accumulators (batched path): folded
        # from the per-run DeviceMetricsRing flush at each run() end
        self._dev_stale_hist = np.zeros(_STALE_BINS, np.int64)
        self._dev_participation = np.zeros(len(self.clients), np.int64)

        self.metrics = MetricsLog(fl_cfg.target_accuracy,
                                  fl_cfg.oscillation_thresholds)
        self.tx_bytes = 0
        self.rx_bytes = 0
        self.staleness_hist: Dict[int, int] = {}
        self.idle_time = 0.0
        self._params_bytes = pytree_bytes(init_params)
        self._state_bytes = pytree_bytes(init_state)
        self._last_update_norm = 0.0

        # ---- flat-buffer server path (every mode, fedasync included) ----
        self.codec = flatbuf.PytreeCodec(init_params,
                                         qblock=fl_cfg.quant_block,
                                         topk_frac=fl_cfg.topk_frac)
        self._flat_params = self.codec.ravel(init_params)
        assert fl_cfg.aggregation in agg.FlatServer.MODES
        # batched semi-async clients keep references to past flat global
        # models (adopted at their upload boundary), so the server must
        # not donate-invalidate its params buffer in that mode
        self._batched_async = (fl_cfg.mode == "semi_async"
                               and fl_cfg.batch_clients)
        # wire format of the upload channel (FLConfig docstring table);
        # compress_updates is the legacy q8 alias
        self._wire = fl_cfg.wire
        if self._wire == "f32" and fl_cfg.compress_updates:
            self._wire = "q8"
        self._quant = self._wire == "q8"
        self._q4 = self._wire == "q4"
        self._topk = self._wire == "topk"
        self._lossy = self._wire != "f32"
        # q4 stochastic rounding: per-client upload counters — the PRNG
        # key of upload n of client c is fold_in(fold_in(key(seed), c),
        # n), drawn inside the jitted quantize program, so the
        # sequential and batched paths reproduce the draws bit-exactly
        self._sr_counter: Dict[int, int] = {}
        # ---- fault injection + server-side defense (PR 8) ----
        # corrupt/byzantine draws ride the scheduler's SchedEvents into
        # the payload appliers (repro.faults.payload); crash/straggler
        # live entirely in the scheduler.  The defense screen runs a
        # fused per-row isfinite+L2 pass (FlatServer.screen) whose
        # verdicts zero or clip a row's aggregation weight through the
        # external_discount path — and, for screened rows, zero the
        # payload (buffered) or skip the fold (streaming): 0 x NaN is
        # NaN, so a zero weight alone cannot contain a poisoned row.
        self._defense = fl_cfg.defense
        self.screened_uploads = 0
        self.clipped_uploads = 0
        self.corrupted_uploads = 0
        self.byzantine_uploads = 0
        self._qbuf = None
        self._tbuf = None
        self._buf = None
        # ---- server channel (tentpole PR 6): streaming vs buffered ----
        # streaming: each upload is folded into an O(D) running partial
        # sum the moment it arrives (AccumBuffer + FlatServer.fold/
        # finalize) — peak channel memory flat in the horizon's upload
        # count.  buffered: the resident (K, D) rows + one reduction (the
        # bit-exact parity oracle).  "auto" picks streaming for the
        # semi-async engine (whose uploads genuinely trickle in) and
        # buffered for SFL (whose round emits its rows as one program).
        self._channel = fl_cfg.server_channel
        if self._channel == "auto":
            self._channel = ("streaming" if fl_cfg.mode == "semi_async"
                             else "buffered")
        self._streaming = self._channel == "streaming"
        # fixed per-horizon upload target: k / queue horizons close on a
        # count, timeout/hybrid on the clock (None — unbounded, streaming
        # only; validate() rejects buffered for those)
        if fl_cfg.horizon == "queue":
            self._horizon_target: Optional[int] = (fl_cfg.horizon_queue
                                                   or fl_cfg.k)
        elif fl_cfg.horizon in ("timeout", "hybrid"):
            self._horizon_target = None
        else:
            self._horizon_target = fl_cfg.k
        # simulated time of the last aggregation (timeout horizons)
        self._last_agg_time = 0.0
        # per-client error-feedback residuals (dq,), created on first upload
        self._residuals: Dict[int, jax.Array] = {}
        # ---- multi-device: flat channel rows over the mesh row axes ----
        # devices=P -> 1-D "pod" mesh; mesh_shape=(E, P) -> hierarchical
        # 2-D (edge, pod) mesh (E=1 builds the identical 1-D mesh, so the
        # alias path is bit-exact)
        self._mesh = None
        row_sh = None
        n_shards = fl_cfg.mesh_devices
        if n_shards > 1:
            assert n_shards <= len(jax.devices()), (
                f"mesh of {n_shards} devices requested but only "
                f"{len(jax.devices())} jax devices visible (on CPU hosts "
                "set XLA_FLAGS=--xla_force_host_platform_device_count "
                "before importing jax)")
            edges, pods = fl_cfg.mesh_shape or (1, fl_cfg.devices)
            self._mesh = shflat.make_hier_mesh(edges, pods)
            row_sh = shflat.row_sharding(self._mesh)
        # the frozen part, once on the device (replicated over a mesh);
        # the trailing argument of every client program that reads it
        self.frozen = None if frozen is None else jax.device_put(
            frozen, None if self._mesh is None else NamedSharding(
                self._mesh, PartitionSpec()))
        self._fz = () if frozen is None else (self.frozen,)
        # discount-at-ingest: the engine composes the FINAL per-upload
        # aggregation weights on host for EVERY mode (_weight_vector) —
        # the (1+tau)^-alpha discount, fedavg data sizes, adaptive policy
        # scores and the fedasync mix rates alike — so the streaming
        # channel can fold them the moment an upload lands and the
        # buffered oracle applies the exact same numbers verbatim
        # (external_discount).  fedasync_rates makes the buffered fedasync
        # step consume those raw rates through the same sequential
        # (1-a)-mix recurrence the streaming fold runs, which is what
        # keeps the two channels bit-exact.
        self._server = agg.FlatServer(
            fl_cfg.aggregation, self.codec.d,
            server_lr=fl_cfg.server_lr, alpha=fl_cfg.staleness_alpha,
            momentum=fl_cfg.server_momentum or 0.8,
            ema_anchor=fl_cfg.ema_anchor or 0.05,
            wire=self._wire, qblock=fl_cfg.quant_block,
            donate=False if self._batched_async else None,
            mesh=self._mesh,
            external_discount=True, fedasync_rates=True)
        self._opt = self._server.init_opt(self._flat_params)
        self._accum = None
        if self._streaming:
            # O(D) double-buffered accumulator: n_rows = mesh shards (the
            # streaming counterpart of the row-sharded (K, D) buffer; on
            # the 2-D mesh each edge group's P rows are that edge's own
            # partial sums — fold-at-edge) — ingestion of horizon r+1
            # overlaps the server step of r.  q8/q4 folds dequantize onto
            # the padded (Dq,) grid; topk scatters into the raw (d,)
            # range (pad coords contribute 0)
            self._accum = flatbuf.AccumBuffer(
                self.codec.dq if self._wire in ("q8", "q4")
                else self.codec.d,
                self._server.fold_program,
                n_rows=n_shards, sharding=row_sh)
        elif self._quant or self._q4:
            self._qbuf = flatbuf.QuantBuffer(self._horizon_target,
                                             self.codec.d,
                                             fl_cfg.quant_block,
                                             sharding=row_sh,
                                             packed=self._q4)
        elif self._topk:
            self._tbuf = flatbuf.TopkBuffer(self._horizon_target,
                                            self.codec.d, self.codec.nk,
                                            fl_cfg.quant_block,
                                            sharding=row_sh)
        else:
            self._buf = flatbuf.alloc_buffer(self._horizon_target,
                                             self.codec.d,
                                             sharding=row_sh)
        # lossy channel, model targets: the non-trainable BN state ships
        # through the ravel_q8 wire format alongside the weights (q4
        # included — the state is tiny next to D, so sub-byte packing of
        # it buys nothing; topk is gradient-only and never lands here).
        # Server-side consumers see the quantize->dequantize roundtrip;
        # clients keep their exact local state.
        self._state_codec = None
        if (self._wire in ("q8", "q4")
                and fl_cfg.aggregation in _MODEL_TARGETS
                and jax.tree_util.tree_leaves(init_state)):
            self._state_codec = flatbuf.PytreeCodec(
                init_state, qblock=fl_cfg.quant_block)
        # resolved lazily by the batched semi-async path ("auto" needs one
        # abstract model trace); recorded for benchmarks / diagnostics
        self.wave_impl_resolved: Optional[str] = None
        # histogram of *real* (pre-bucketing) wave sizes, for the
        # compile-count diagnostics
        self.wave_size_hist: Dict[int, int] = {}
        # batched mode defers the per-round unravel; run() materializes
        # the global pytree once at the end
        self._global_stale = False
        # device-resident (n_clients, ...) shard bank for the batched
        # path, built once on first use (waves gather rows in-program)
        self._shard_bank = None
        # the semi-async event heap (inside self.sched) persists across
        # run() calls, so incremental runs (run(5) then run(10)) continue
        # ONE simulated schedule instead of re-jittering and restarting
        # simulated time.  Batched-mode client weights (flat (D,) rows)
        # persist alongside it — the counterpart of ClientState.params on
        # the sequential path.
        self._client_flats: Optional[List[jax.Array]] = None
        # batched client program of the last run — the semi-async wave of
        # the resolved (impl, mesh) combo, or the sync round (one wave of
        # K clients) — obs.profile.engine_compile_log tracks its compiles
        self._wave_fn = None
        # wall-clock seconds spent inside run() (obs folds/sec gauge)
        self.wall_run_s = 0.0
        # ---- observability (tentpole PR 10): host-side span tracer ----
        # trace_level="off" never constructs a tracer, so the untraced
        # engine is bit-exact with pre-obs builds; tracing on adds only
        # host bookkeeping (every site is `if tracer is not None`-gated)
        self.tracer = None
        if fl_cfg.trace_level != "off":
            from repro.obs.trace import SpanTracer
            self.tracer = SpanTracer(
                fl_cfg.trace_dir, fl_cfg.trace_level,
                meta=dict(mode=fl_cfg.mode, aggregation=fl_cfg.aggregation,
                          wire=self._wire, channel=self._channel,
                          horizon=fl_cfg.horizon, defense=self._defense,
                          n_clients=len(self.clients), k=fl_cfg.k,
                          d=self.codec.d, seed=fl_cfg.seed))
            self.sched.tracer = self.tracer

    # ------------------------------------------------------------------
    def _base_compute(self, c: ClientState) -> float:
        """Deterministic simulated compute seconds for one upload period
        (local_epochs) of c — the base the sched timing models jitter.
        Reads ``c.speed`` at call time: the scheduler's event queue
        snapshots speeds and rescales pending events when they are
        mutated across run() calls (sched.events.EventQueue.resume)."""
        per_epoch = c.n_samples / (_BASE_RATE * c.speed)
        return per_epoch * self.cfg.local_epochs

    def _agg_overhead(self) -> float:
        # FedAvg-style aggregation bookkeeping (the data-volume query and
        # per-client weighting coefficients, paper §5.1.2 Table 2) adds
        # server-side latency that scales with the number of buffered
        # updates — modeled as 0.05 simulated seconds per buffered upload.
        # FedSGD's unweighted gradient mean needs no per-client
        # bookkeeping and pays a flat 0.01 s.
        return 0.05 * self.cfg.k if self.cfg.aggregation != "fedsgd" else 0.01

    def _fold_shard(self, slot: int) -> int:
        """Accumulator row for the streaming fold of upload ``slot``.

        With a fixed, evenly divisible horizon target the assignment is
        block-wise — slot i folds into the row that holds the rows the
        buffered channel would shard to the same mesh shard (on the 2-D
        mesh: shard e*P + p of edge e, so each edge accumulates exactly
        the rows the buffered channel lays on it) — so the per-shard
        partial sums (and hence the mesh server round) match the buffered
        oracle bitwise.  Clock-triggered horizons round-robin instead.
        fedasync always folds into row 0: its sequential mix is one
        non-commuting chain, not a per-shard decomposition."""
        if self._mesh is None or self.cfg.aggregation == "fedasync":
            return 0
        n = self.cfg.mesh_devices
        t = self._horizon_target
        if t is not None and t % n == 0:
            return min(slot // (t // n), n - 1)
        return slot % n

    def _horizon_due(self, count: int, now: float) -> bool:
        """Aggregation-horizon trigger (``FLConfig.horizon``): close on
        the paper's K-count, an explicit queue length, a wall-clock
        timeout since the last aggregation (SEAFL-style periodic
        aggregation — needs at least one buffered upload), or whichever
        of queue/timeout fires first (hybrid)."""
        if count <= 0:
            return False
        cfg = self.cfg
        if cfg.horizon in ("k", "queue"):
            return count >= self._horizon_target
        timed = now >= self._last_agg_time + cfg.horizon_timeout_s
        if cfg.horizon == "timeout":
            return timed
        return timed or count >= (cfg.horizon_queue or cfg.k)  # hybrid

    def _run_local(self, c: ClientState):
        """Run one local 'upload period' (local_epochs) for client c.
        The returned loss is a device scalar — never fetched in the
        engine loop."""
        shard = self.shards[c.cid]
        params, state = c.params, c.model_state
        loss = jnp.float32(0.0)
        for _ in range(self.cfg.local_epochs):
            params, state, loss = self.epoch_fn(
                params, state, shard["xs"], shard["ys"], shard["mask"],
                self.cfg.client_lr, *self._fz)
        return params, state, loss

    # ------------------------------------------------------------------
    def _upload_nbytes(self) -> int:
        """Channel cost of one upload, per target — the wire-format rule
        of :func:`repro.kernels.quantize.payload_nbytes` (q8: int8 values
        + block scales; q4: two lanes per byte; topk: index+value pairs
        over the kept coords).  For model targets that includes the
        non-trainable state (BN running stats), which rides the ravel_q8
        wire format on every lossy wire."""
        model_target = self.cfg.aggregation in _MODEL_TARGETS
        if self._lossy:
            payload = payload_nbytes(
                self._wire, d=self.codec.d, dq=self.codec.dq,
                n_qblocks=self.codec.n_qblocks, nk=self.codec.nk,
                nk_qblocks=self.codec.nk_qblocks)
        else:
            payload = self._params_bytes
        if model_target:
            if self._state_codec is not None:
                state_payload = (self._state_codec.dq
                                 + self._state_codec.n_qblocks * 4)
            else:
                state_payload = self._state_bytes
            return int((payload + state_payload)
                       * (1 + _MODEL_ENVELOPE))
        return int(payload * (1 + _GRAD_ENVELOPE))

    def _state_q8(self, state: Pytree) -> Pytree:
        """Server-side view of an uploaded model-target state: the
        quantize->dequantize roundtrip of the int8 state payload (identity
        when the channel is f32 or the state is empty)."""
        if self._state_codec is None:
            return state
        return self._state_codec.roundtrip_q8(state)

    def _state_q8_rows(self, states: Pytree) -> Pytree:
        """K-stacked variant for the batched wave / SFL round states."""
        if self._state_codec is None:
            return states
        return self._state_codec.roundtrip_q8_rows(states)

    def _residual(self, cid: int) -> jax.Array:
        """Client-side error-feedback residual (zeros before the client's
        first upload)."""
        res = self._residuals.get(cid)
        return res if res is not None else self.codec.zero_residual()

    def _next_counter(self, cid: int) -> int:
        """q4 stochastic-rounding upload counter for client ``cid``.
        Strictly per-client, so the counter a given upload draws with
        depends only on how many uploads that client made before — the
        invariant that keeps the sequential and batched engine paths
        (which consume counters in different global orders) bit-identical."""
        n = self._sr_counter.get(cid, 0)
        self._sr_counter[cid] = n + 1
        return n

    # ---------------- fault injection + defense (PR 8) ----------------

    def _apply_payload_faults(self, payload: tuple, faults: List) -> tuple:
        """Apply corrupt/byzantine draws to one K-stacked wave of wire
        payload rows (K=1 on the sequential path) — AFTER the
        error-feedback residual update, so the client believes it sent a
        clean row (a wire-level fault).  The appliers are shared
        elementwise jnp programs whose untouched lanes come back bitwise
        identical, which keeps the no-fault lanes (and both engine
        paths) exact.  No-op without any fault in the wave."""
        if not any(f is not None for f in faults):
            return payload
        corrupt = [f is not None and f.kind == "corrupt" for f in faults]
        byz = [f is not None and f.kind == "byzantine" for f in faults]
        locs = [f.loc if f is not None else 0.0 for f in faults]
        self.corrupted_uploads += sum(corrupt)
        self.byzantine_uploads += sum(byz)
        resc = self.cfg.fault_byzantine_rescale
        if self._wire == "f32":
            return (faultsmod.apply_faults_flat(payload[0], corrupt, byz,
                                                locs, resc),)
        if self._topk:
            idx, qv, s = payload
            qv, s = faultsmod.apply_faults_q(qv, s, corrupt, byz, locs,
                                             resc)
            return (idx, qv, s)
        q, s = payload
        return faultsmod.apply_faults_q(q, s, corrupt, byz, locs, resc)

    def _screen_factors(self, payload: tuple, kreal: int) -> np.ndarray:
        """Defense verdicts for ``kreal`` payload rows (extra rows are
        bucketed-wave padding lanes — screened but never counted or
        applied): the fused per-row sum-of-squares pass, then the host
        screen/clip factor composition (repro.faults.defense).  Returns
        the (kreal,) np.float32 weight factors."""
        sumsq = np.asarray(self._server.screen(payload))
        fac, ns, ncl = faultsmod.defense_factors(
            sumsq[:kreal], self._defense, self.cfg.defense_norm_cap)
        self.screened_uploads += ns
        self.clipped_uploads += ncl
        return fac

    def _zero_screened_rows(self, payload: tuple, mask) -> tuple:
        """Zero the PAYLOAD of screened rows before the buffered scatter
        (the streaming channel skips the fold instead): the f32 row
        itself, or — on every lossy wire — the per-block scales, since
        dequantizing any int payload against scale 0 is exactly 0.
        ``jnp.where`` returns unmasked lanes bitwise untouched."""
        mask = jnp.asarray(mask)[:, None]
        if self._wire == "f32":
            return (jnp.where(mask, jnp.float32(0.0), payload[0]),)
        return payload[:-1] + (jnp.where(mask, jnp.float32(0.0),
                                         payload[-1]),)

    def _enqueue_upload(self, buffer: List[Dict], c: ClientState,
                        w_end, s_end, staleness: int,
                        fault=None) -> None:
        """Serialize one client upload.  Buffered channel: ravel the
        update and write it into the row for the next free slot (the
        buffer is donated — an in-place device write).  Streaming
        channel: fold it into the running O(D) partial sum on arrival,
        with its FINAL aggregation weight (discount-at-ingest).  With the
        quantized channel the payload is int8 + block scales from one
        fused program either way, and the error-feedback residual stays
        client-side.  Must be called before ``c.params`` is refreshed
        (gradient targets diff against the client's round-start
        weights).  ``fault`` is an optional corrupt/byzantine FaultDraw
        applied to the serialized payload; with a defense configured the
        row is screened before it can touch the channel."""
        cfg = self.cfg
        entry: Dict = {"staleness": staleness, "cid": c.cid,
                       "n": c.n_samples}
        if cfg.aggregation in _MODEL_TARGETS:
            if self._quant:
                # model target: quantize the weights themselves (weights do
                # not accumulate across rounds — no error feedback); the
                # BN state ships int8 too — the server sees its roundtrip
                q, s = self.codec.ravel_q8_nores(w_end)
                payload = (q, s)
                s_end = self._state_q8(s_end)
            elif self._q4:
                p, s = self.codec.ravel_q4_nores(
                    w_end, cfg.seed, c.cid, self._next_counter(c.cid))
                payload = (p, s)
                s_end = self._state_q8(s_end)
            else:  # topk is gradient-only (FLConfig.validate)
                payload = (self.codec.ravel(w_end),)
        else:  # gradient targets: fedsgd, sdga, fedbuff, fedopt
            if self._quant:
                # ONE fused program: diff + ravel + EF add + blockwise
                # absmax int8 quantize; residual = what this round dropped
                if cfg.error_feedback:
                    q, s, new_res = self.codec.ravel_delta_q8(
                        c.params, w_end, cfg.client_lr,
                        self._residual(c.cid))
                    self._residuals[c.cid] = new_res
                else:
                    q, s = self.codec.ravel_delta_q8_nores(
                        c.params, w_end, cfg.client_lr)
                payload = (q, s)
            elif self._q4:
                # same fused shape, stochastic rounding keyed per
                # (client, upload counter) — see _next_counter
                ctr = self._next_counter(c.cid)
                if cfg.error_feedback:
                    p, s, new_res = self.codec.ravel_delta_q4(
                        c.params, w_end, cfg.client_lr,
                        self._residual(c.cid), cfg.seed, c.cid, ctr)
                    self._residuals[c.cid] = new_res
                else:
                    p, s = self.codec.ravel_delta_q4_nores(
                        c.params, w_end, cfg.client_lr, cfg.seed,
                        c.cid, ctr)
                payload = (p, s)
            elif self._topk:
                # sparse wire: the residual carries the dropped coords in
                # full plus the value-quantization error
                if cfg.error_feedback:
                    idx, qv, s, new_res = self.codec.ravel_delta_topk(
                        c.params, w_end, cfg.client_lr,
                        self._residual(c.cid))
                    self._residuals[c.cid] = new_res
                else:
                    idx, qv, s = self.codec.ravel_delta_topk_nores(
                        c.params, w_end, cfg.client_lr)
                payload = (idx, qv, s)
            else:
                payload = (self.codec.ravel_delta(c.params, w_end,
                                                  cfg.client_lr),)
        if fault is not None:
            # the appliers are row-stacked (shared with the batched
            # wave); lift the single upload to K=1 and back
            payload = tuple(a[0] for a in self._apply_payload_faults(
                tuple(a[None] for a in payload), [fault]))
        fac = None
        if self._defense != "none":
            fac = self._screen_factors(tuple(a[None] for a in payload),
                                       1)[0]
            entry["fac"] = fac
        slot = len(buffer)
        if self._streaming:
            # accumulate-on-arrival: the upload's final weight (and, for
            # fedasync, the 1-a survival factor) fold NOW — the horizon's
            # server round is just a finalize over the partial sums.  A
            # screened row (factor 0) never folds at all: skip() records
            # the arrival with an exact 0.0 weight, keeping the finalize
            # reduction tree identical to the buffered oracle's
            if fac is not None and fac == np.float32(0.0):
                self._accum.skip(shard=self._fold_shard(slot),
                                 staleness=staleness)
            else:
                w = self._weight_vector([staleness], [c.n_samples])[0]
                if fac is not None:
                    w = np.float32(w * fac)
                beta = (np.float32(1.0) - w
                        if cfg.aggregation == "fedasync" else 1.0)
                self._accum.fold(payload, w=w, beta=beta,
                                 shard=self._fold_shard(slot),
                                 staleness=staleness)
        else:
            if fac is not None and fac == np.float32(0.0):
                payload = self._zero_screened_rows(
                    tuple(a[None] for a in payload), np.ones(1, bool))
                payload = tuple(a[0] for a in payload)
            if self._quant or self._q4:
                self._qbuf.write(*payload, slot)
            elif self._topk:
                self._tbuf.write(*payload, slot)
            else:
                self._buf = flatbuf.write_slot(self._buf, payload[0],
                                               jnp.int32(slot))
        entry["state"] = s_end
        self.tx_bytes += self._upload_nbytes()
        buffer.append(entry)

    # ------------------------------------------------------------------
    def _weight_vector(self, staleness: Sequence[int],
                       sizes: Sequence[int]) -> np.ndarray:
        """FINAL per-upload aggregation weights, np.float32 on host
        (discount-at-ingest).

        Every mode's weighting — fedavg data sizes, fedsgd units, the
        (1+tau)^-alpha discount of the staleness modes, fedasync's raw
        mix rates a_i = clip(fedasync_alpha * (1+tau)^-alpha * score,
        0, 1) — times any adaptive policy score, composed from host ints
        with no device sync.  Both channels consume these verbatim: the
        streaming channel folds weight i the moment upload i arrives
        (``_weight_vector([tau], [n])[0]`` — numpy's scalar and vector
        kernels agree bitwise), the buffered oracle applies the whole
        vector in its one reduction (``external_discount=True``,
        ``fedasync_rates=True``), which is what makes the two channels
        bit-exact against each other."""
        cfg = self.cfg
        policy = self.sched.policy
        score = (policy.score(staleness, sizes)
                 if policy.reweights else None)
        stal = np.asarray(staleness, np.float32)
        if cfg.aggregation == "fedasync":
            a = cfg.fedasync_alpha * np.power(
                stal + 1.0, -np.float32(cfg.staleness_alpha))
            if score is not None:
                a = np.clip(a * np.asarray(score, np.float32), 0.0, 1.0)
            return np.asarray(a, np.float32)
        if cfg.aggregation == "fedavg":
            base = np.asarray(sizes, np.float32)
        elif cfg.aggregation == "fedsgd":
            base = np.ones((len(staleness),), np.float32)
        else:  # fedbuff / fedopt / sdga: the poly discount
            base = np.power(stal + 1.0, -np.float32(cfg.staleness_alpha))
        if score is not None:
            base = base * np.asarray(score, np.float32)
        return np.asarray(base, np.float32)

    def _record_staleness(self, staleness: Sequence[int]) -> None:
        for s in staleness:
            s = int(s)
            self.staleness_hist[s] = self.staleness_hist.get(s, 0) + 1

    def _broadcast_bytes(self) -> None:
        # broadcast of the new global model to all clients
        self.rx_bytes += int((self._params_bytes + self._state_bytes)
                             * len(self.clients))

    def _server_round(self, staleness: Sequence[int],
                      sizes: Sequence[int],
                      facs: Optional[Sequence[np.float32]] = None
                      ) -> Dict[str, jax.Array]:
        """Buffered-channel server round: ONE jitted flat program + host
        bookkeeping, shared by the sequential and horizon-batched paths.
        Returns the round's device metric scalars (update_norm) without
        fetching them.  ``facs`` are the defense layer's per-row weight
        factors (screen zeros / clip ratios), composed into the weight
        vector with the same elementwise np.float32 multiply the
        streaming channel applies per upload — bitwise the same final
        weights."""
        self._record_staleness(staleness)
        w = self._weight_vector(staleness, sizes)
        if facs is not None:
            w = w * np.asarray(facs, np.float32)
        wvec = jnp.asarray(w)
        if self._qbuf is not None:
            buf = self._qbuf.views
        elif self._tbuf is not None:
            buf = self._tbuf.views
        else:
            buf = self._buf
        self._flat_params, self._opt, m = self._server.step(
            self._flat_params, buf, wvec, self._opt)
        self.t_global += 1
        self._broadcast_bytes()
        return m

    def _server_round_streaming(
            self, staleness: Sequence[int]) -> Dict[str, jax.Array]:
        """Streaming-channel server round: every upload already folded at
        ingest, so this is seal (swap the double-buffered accumulator —
        horizon r+1 folds while this round's programs drain) + ONE
        finalize from the O(D) partial sums + release of the zeroed
        bank."""
        self._record_staleness(staleness)
        bank, wvec, stats = self._accum.seal()
        self._flat_params, self._opt, m, zeroed = self._server.finalize(
            self._flat_params, bank, wvec, self._opt,
            pprod=stats["pprod"])
        self._accum.release(zeroed)
        self.t_global += 1
        self._broadcast_bytes()
        return m

    def _aggregate(self, buffer: List[Dict],
                   states_stacked: Optional[Pytree] = None):
        """Sequential-path aggregation: flat server round + non-trainable
        state handling + per-round unravel of the global pytree."""
        cfg = self.cfg
        stal = [b["staleness"] for b in buffer]
        if self._streaming:
            m = self._server_round_streaming(stal)
        else:
            facs = ([b["fac"] for b in buffer]
                    if self._defense != "none" else None)
            m = self._server_round(stal, [b["n"] for b in buffer], facs)
        self.global_params = self.codec.unravel(self._flat_params)
        self._last_update_norm = m["update_norm"]

        # non-trainable state (BN running stats) rides the tree path — it
        # is tiny next to D and structurally heterogeneous
        if cfg.aggregation == "fedavg":
            if states_stacked is None and buffer and "state" in buffer[0]:
                states_stacked = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs),
                    *[b["state"] for b in buffer])
            if (states_stacked is not None
                    and jax.tree_util.tree_leaves(states_stacked)):
                sizes = jnp.asarray([b["n"] for b in buffer], jnp.float32)
                self.global_state = agg.weighted_mean(states_stacked, sizes)
        else:
            # gradient targets and fedasync adopt the newest buffered state
            if states_stacked is not None:
                self.global_state = jax.tree_util.tree_map(
                    lambda s: s[-1], states_stacked)
            else:
                self.global_state = buffer[-1].get("state",
                                                   self.global_state)
        return m

    def _wave_bucket(self, kw: int) -> int:
        """Wave-size bucket: the next power of two >= kw (capped at the
        horizon's upload target when one exists — clock-triggered
        horizons have no fixed ceiling), so high-churn schedules compile
        O(log K) distinct wave programs instead of one per distinct wave
        size; identity with ``wave_buckets=False`` (the unbucketed parity
        oracle)."""
        if not self.cfg.wave_buckets:
            return kw
        b = 1 << (kw - 1).bit_length()
        t = self._horizon_target
        return b if t is None else min(b, t)

    def _eval_due(self, rnd: int, n_rounds: int) -> bool:
        """Evaluate every eval_every-th aggregation + always the last."""
        return rnd % self.cfg.eval_every == 0 or rnd == n_rounds

    def _eval_and_record(self, now: float, stale_vals: Sequence[int]) -> None:
        acc, loss, *report = self.eval_fn(self.global_params,
                                          self.global_state, self.test_x,
                                          self.test_y, *self._fz)
        acc, loss = float(acc), float(loss)
        nan_event = not np.isfinite(loss)
        self.metrics.record(
            reported=_floats(report[0] if report else {}),
            round=self.t_global, sim_time=now, accuracy=acc, loss=loss,
            tx_bytes=self.tx_bytes, rx_bytes=self.rx_bytes,
            mean_staleness=float(np.mean(stale_vals)) if stale_vals else 0.0,
            max_staleness=int(max(stale_vals)) if stale_vals else 0,
            nan_event=nan_event,
            update_norm=float(self._last_update_norm),
            screened_uploads=self.screened_uploads,
            clipped_uploads=self.clipped_uploads)

    def _trace_round(self, stal: Sequence[int], sizes: Sequence[int],
                     facs, t0: float, t1: float) -> None:
        """Emit the horizon-close aggregate/round spans and flush the
        tracer's pending records (tracing on only).  Recomputes the
        final per-upload weight vector on host — the same
        ``_weight_vector`` x defense-factor product both channels
        consume — so ingest records carry the exact folded weights."""
        w = self._weight_vector(stal, sizes)
        if facs is not None:
            w = w * np.asarray(
                [np.float32(1.0) if f is None else f for f in facs],
                np.float32)
        self.tracer.round(
            self.t_global, t0=t0, t1=t1, agg_s=self._agg_overhead(),
            k=len(stal), staleness=stal,
            weights=[float(x) for x in w],
            counts=dict(tx_bytes=int(self.tx_bytes),
                        rx_bytes=int(self.rx_bytes),
                        screened=int(self.screened_uploads),
                        clipped=int(self.clipped_uploads),
                        corrupted=int(self.corrupted_uploads),
                        byzantine=int(self.byzantine_uploads)))

    # ------------------------------------------------------------------
    def run(self, n_rounds: int, log_every: int = 0) -> FLResult:
        with _span("safl.run") as run_span:
            t0, wall0 = self.t_global, _walltime.perf_counter()
            if self.cfg.mode == "sync":
                self._run_sync(n_rounds, log_every)
            elif self.cfg.batch_clients:
                self._run_semi_async_batched(n_rounds, log_every)
            else:
                self._run_semi_async(n_rounds, log_every)
            self.wall_run_s += _walltime.perf_counter() - wall0
            if self.tracer is not None:
                # flush events of a horizon left open at run end (they
                # stay pending across incremental run() calls otherwise)
                self.tracer.tail()
            if self._global_stale:
                # flat end-to-end: the ONE unravel of the whole run
                with _span("safl.unravel"):
                    self.global_params = self.codec.unravel(
                        self._flat_params)
                self._global_stale = False
            stats = self.sched.stats()
            stats["staleness_bins"] = self._dev_stale_hist.copy()
            # fault/defense accounting (engine side; crashed_uploads comes
            # from the scheduler's own stats above)
            stats["screened_uploads"] = self.screened_uploads
            stats["clipped_uploads"] = self.clipped_uploads
            stats["corrupted_uploads"] = self.corrupted_uploads
            stats["byzantine_uploads"] = self.byzantine_uploads
            run_span.set_metadata(rounds=self.t_global - t0)
        return FLResult(self.metrics, self.global_params,
                        self.staleness_hist, self.idle_time,
                        participation=self.sched.participation.copy(),
                        sched_stats=stats)

    # ----- SFL -----
    def _run_sync(self, n_rounds: int, log_every: int) -> None:
        cfg = self.cfg
        # the whole K-client round as one vmapped program; with the
        # quantized channel the K rows are quantized in one vmapped
        # program too (same per-row math as the sequential path)
        batched = cfg.batch_clients
        if batched:
            target = ("params" if cfg.aggregation in _MODEL_TARGETS
                      else "grad")
            round_fn = make_batched_local_train(
                self.apply_fn, self.kind, target, cfg.local_epochs,
                mesh=self._mesh)
            self._wave_fn = round_fn
        now = 0.0
        for _ in range(n_rounds):
            active = self.rng.choice(len(self.clients), cfg.k,
                                     replace=False)
            buffer: List[Dict] = []
            durations = []
            states_k = None
            if batched:
                xs_k = np.stack([self.shards[cid]["xs"] for cid in active])
                ys_k = np.stack([self.shards[cid]["ys"] for cid in active])
                mask_k = np.stack([self.shards[cid]["mask"]
                                   for cid in active])
                vecs, states_k, _losses = round_fn(
                    self.global_params, self.global_state, xs_k, ys_k,
                    mask_k, cfg.client_lr, *self._fz)
                if target == "params":
                    # the server sees the int8-shipped state roundtrip
                    # (identity on the f32 channel)
                    states_k = self._state_q8_rows(states_k)
                if self._lossy:
                    # quantize all K rows in one vmapped program; gradient
                    # targets thread their error-feedback residuals through
                    use_ef = (cfg.error_feedback
                              and cfg.aggregation not in _MODEL_TARGETS)
                    if use_ef:
                        res = jnp.stack([self._residual(int(cid))
                                         for cid in active])
                    if self._quant:
                        if use_ef:
                            q, s, new_res = self.codec.quantize_rows(vecs,
                                                                     res)
                        else:
                            q, s = self.codec.quantize_rows_nores(vecs)
                        self._qbuf.set_rows(q, s)
                    elif self._q4:
                        # per-lane (cid, counter) keys — the same draws
                        # the sequential path's per-upload calls make
                        cids_v = jnp.asarray(active, jnp.int32)
                        ctrs = jnp.asarray(
                            [self._next_counter(int(cid))
                             for cid in active], jnp.int32)
                        if use_ef:
                            q, s, new_res = self.codec.quantize_rows_q4(
                                vecs, res, cfg.seed, cids_v, ctrs)
                        else:
                            q, s = self.codec.quantize_rows_q4_nores(
                                vecs, cfg.seed, cids_v, ctrs)
                        self._qbuf.set_rows(q, s)
                    else:  # topk (gradient-only, so use_ef governs)
                        if use_ef:
                            ti, tq, ts, new_res = \
                                self.codec.quantize_rows_topk(vecs, res)
                        else:
                            ti, tq, ts = \
                                self.codec.quantize_rows_topk_nores(vecs)
                        self._tbuf.set_rows(ti, tq, ts)
                    if use_ef:
                        for row, cid in enumerate(active):
                            self._residuals[int(cid)] = new_res[row]
                else:
                    self._buf = vecs  # this round's (K, D) buffer
                for cid in active:
                    c = self.clients[cid]
                    c.params, c.model_state = (self.global_params,
                                               self.global_state)
                    c.version = self.t_global
                    self.tx_bytes += self._upload_nbytes()
                    buffer.append({"staleness": 0, "cid": cid,
                                   "n": c.n_samples})
                    durations.append(self.sched.timing.sync_duration(c))
                    self.sched.participation[cid] += 1
            else:
                for cid in active:
                    c = self.clients[cid]
                    c.params, c.model_state = (self.global_params,
                                               self.global_state)
                    c.version = self.t_global
                    w_end, s_end, _ = self._run_local(c)
                    self._enqueue_upload(buffer, c, w_end, s_end, 0)
                    durations.append(self.sched.timing.sync_duration(c))
                    self.sched.participation[cid] += 1
            round_t = max(durations) + self._agg_overhead()
            self.idle_time += sum(round_t - d for d in durations)
            t_open = now
            now += round_t
            self._aggregate(buffer, states_stacked=states_k)
            if self.tracer is not None:
                # SFL uploads: every active client trains from t_open;
                # sync_duration = compute + comm splits the sub-spans
                nb = self._upload_nbytes()
                for slot, cid in enumerate(active):
                    c = self.clients[cid]
                    d = durations[slot]
                    comm = min(c.comm_time, d)
                    self.tracer.upload(
                        slot=slot, cid=int(cid), t=t_open + d,
                        compute_s=d - comm, comm_s=comm, staleness=0,
                        nbytes=nb, wire=self._wire, fac=None)
                self._trace_round([0] * len(buffer),
                                  [b["n"] for b in buffer], None,
                                  t_open, now - self._agg_overhead())
            if self._eval_due(self.t_global, n_rounds):
                self._eval_and_record(now, [0] * len(buffer))
                if log_every and self.t_global % log_every == 0:
                    r = self.metrics.records[-1]
                    print(f"  [SFL-{cfg.aggregation}] round {r.round} "
                          f"acc={r.accuracy:.4f} loss={r.loss:.4f}")

    # ----- SAFL: sequential per-upload path (the parity oracle) -----
    def _run_semi_async(self, n_rounds: int, log_every: int) -> None:
        """Per-upload loop over the scheduler's event stream.  The
        scheduler owns the heap (WAKE no-shows are consumed internally,
        every pop schedules the client's successor event) and surfaces
        one upload *decision* per pop; a policy-rejected upload discards
        the client's local progress and resyncs it to the current global
        model (selective training — see repro.sched.policy)."""
        self.sched.resume()
        buffer: List[Dict] = []
        now = 0.0
        while self.t_global < n_rounds:
            ev = self.sched.pop(self.t_global)
            if ev is None:
                break
            now, cid = ev.time, ev.cid
            c = self.clients[cid]
            if not ev.admitted:
                # "reject" discards local progress + resyncs (selective
                # training); "crash" is the same reset via the fault
                # layer (the rebooted client re-enqueues after backoff);
                # "idle" is pure back-pressure — the client keeps its
                # local chain and retries from where it is
                if ev.verdict != "idle":
                    c.params, c.model_state = (self.global_params,
                                               self.global_state)
                    c.version = self.t_global
            else:
                w_end, s_end, _ = self._run_local(c)
                self._enqueue_upload(buffer, c, w_end, s_end, ev.staleness,
                                     fault=ev.fault)
                if self.tracer is not None:
                    self.tracer.upload(
                        slot=len(buffer) - 1, cid=cid, t=ev.time,
                        compute_s=ev.compute_s, comm_s=c.comm_time,
                        staleness=ev.staleness,
                        nbytes=self._upload_nbytes(), wire=self._wire,
                        fac=buffer[-1].get("fac"))

                # client-side model refresh (paper §2.2.2): adopt newest
                # global if one arrived since this client's version, else
                # continue local
                if c.version < self.t_global:
                    c.params, c.model_state = (self.global_params,
                                               self.global_state)
                    c.version = self.t_global
                else:
                    c.params, c.model_state = w_end, s_end

            # the horizon check runs on EVERY event's clock, admitted or
            # not: under rate control every over-limit upload idles, so a
            # timeout horizon that only looked at admitted-event times
            # would never see the deadline pass (livelock).  For count
            # horizons this is a no-op — rejections don't grow the buffer.

            if self._horizon_due(len(buffer), now):
                stale_vals = [b["staleness"] for b in buffer]
                sizes = [b["n"] for b in buffer]
                facs = ([b["fac"] for b in buffer]
                        if self._defense != "none" else None)
                t_open = self._last_agg_time
                self._aggregate(buffer)
                self._last_agg_time = now
                if self.tracer is not None:
                    self._trace_round(stale_vals, sizes, facs, t_open, now)
                if self._eval_due(self.t_global, n_rounds):
                    self._eval_and_record(now + self._agg_overhead(),
                                          stale_vals)
                    if log_every and self.t_global % log_every == 0:
                        r = self.metrics.records[-1]
                        print(f"  [SAFL-{self.cfg.aggregation}] "
                              f"round {r.round} acc={r.accuracy:.4f} "
                              f"loss={r.loss:.4f} "
                              f"stale={r.mean_staleness:.2f}")
                buffer = []

    # ----- SAFL: horizon-batched path (the hot path) -----
    def _run_semi_async_batched(self, n_rounds: int, log_every: int) -> None:
        """Pop the heap to each aggregation horizon (K events), run the
        horizon's local trainings as one vmapped program per *wave*
        (event #j of a client within the horizon is wave j — wave 0 is
        nearly everything in steady state), scatter each wave's rows into
        the buffer, and run the fused server round — with eval gated by
        ``eval_every`` and every metric scalar staying on device until the
        run-end ring flush.  Waves are power-of-two bucketed
        (``wave_buckets``): padding lanes duplicate a real lane's inputs
        and scatter to the dropped slot K, so compilation is bounded at
        O(log K) wave programs with unchanged numerics.

        Each stage runs inside a ``safl.*`` profiler span (the table in
        ``repro/obs/README.md``)."""
        cfg = self.cfg
        target = "params" if cfg.aggregation in _MODEL_TARGETS else "grad"
        if self.wave_impl_resolved is None:
            self.wave_impl_resolved = resolve_wave_impl(
                cfg.wave_impl, self.apply_fn, self.global_params,
                self.global_state, self.test_x[:1], self.frozen)
        wave_fn = make_batched_hetero_train(
            self.apply_fn, self.kind, target, cfg.local_epochs, self.codec,
            impl=self.wave_impl_resolved, mesh=self._mesh)
        # exposed for compile-count tracking (obs.profile.engine_compile_log)
        self._wave_fn = wave_fn
        eval_fn = make_flat_eval_fn(self.apply_fn, self.kind, self.codec)
        use_ef = (self._lossy and cfg.error_feedback and target == "grad")
        # device-resident shard bank: one (n_clients, ...) stack built
        # once per engine, gathered per wave — no per-horizon restacking
        if self._shard_bank is None:
            self._shard_bank = tuple(
                jnp.asarray(np.stack([s[f] for s in self.shards]))
                for f in ("xs", "ys", "mask"))
        xs_all, ys_all, mask_all = self._shard_bank
        # clients carry their weights as flat (D,) rows (shared immutable
        # arrays — adopting the global model is a reference, not a copy;
        # the server is constructed donate=False in this mode, see
        # __init__, so adopted rows stay valid across rounds).  The list
        # persists across run() calls, like ClientState.params does on
        # the sequential path.
        if self._client_flats is None:
            self._client_flats = [self._flat_params] * len(self.clients)
        flats = self._client_flats
        # channels: acc, loss, update_norm + the defense layer's
        # cumulative screened/clipped upload counts (f32 scalars — exact
        # for any realistic count)
        with _span("safl.ring"):
            ring = DeviceMetricsRing(n_rounds + 1, channels=5,
                                     stale_bins=_STALE_BINS,
                                     n_clients=len(self.clients))
        pending: List[Dict] = []  # host-side fields per recorded round
        # per recorded round, the counters the model reports from its
        # evaluation (models that report any; fetched with the ring)
        reports: List[Dict[str, jax.Array]] = []

        # model-state leaves per client (the span stat `leaves`; 0 for a
        # model without BatchNorm)
        state_leaves = len(jax.tree_util.tree_leaves(self.global_state))
        self.sched.resume()
        while self.t_global < n_rounds:
            r = self.t_global
            # the server step below advances t_global to r + 1: the
            # round index the SpanTracer records for this horizon
            with _span("safl.round", round=r + 1) as round_span:
                # ---- pop the scheduler to the aggregation horizon (K
                # admitted uploads); the scheduler re-pushes successor
                # events at pop time from schedule data only, so the heap
                # evolves exactly as in the sequential path.
                # Policy-rejected uploads are handled inline: the client
                # discards its local progress and adopts the round-r
                # global model (selective training) — which is also what
                # makes a later ADMITTED event of the same client this
                # horizon train from the adopted weights. ----
                events: List[Tuple[float, int]] = []
                stal: List[int] = []
                evfaults: List = []  # per admitted slot: FaultDraw or None
                evcomp: List[float] = []  # per admitted slot: compute s
                n_adm: Dict[int, int] = {}  # admitted events per cid
                # discard-and-resync decisions (reject / crash) landing
                # AFTER a client's admitted event of this horizon cannot
                # reset the client inline — its earlier training still
                # has to run.  The reset lands between its wave lanes
                # instead: the client's next admitted lane restarts from
                # the round-r global row (force_global), and a reset with
                # no later admitted event leaves the client on the global
                # model when the horizon closes (resync_after) — exactly
                # where the sequential oracle's inline reset puts it.
                force_global: set = set()  # (cid, wave) lanes
                resync_after: set = set()  # cids reset after last lane
                # the horizon clock advances on EVERY popped event,
                # admitted or not — under rate control the deadline of a
                # timeout horizon is typically crossed by an idled
                # upload, and the sequential oracle stamps _last_agg_time
                # with that event's time, so the batched path must too
                # (count horizons never fire on a non-admitted pop: the
                # buffer didn't grow)
                t_pop = 0.0
                popped = 0
                with _span("safl.pop") as pop_span:
                    while not (events
                               and self._horizon_due(len(events), t_pop)):
                        ev = self.sched.pop(r)
                        if ev is None:
                            break
                        popped += 1
                        t_pop = ev.time
                        if not ev.admitted:
                            if ev.verdict == "idle":
                                # back-pressure: nothing changes for the
                                # client — its wave chain (and version)
                                # stay intact, only the horizon clock
                                # advanced
                                continue
                            # "reject" (selective training) and "crash"
                            # (fault layer) both discard the client's
                            # local progress and resync it to the
                            # round-r global model
                            k_adm = n_adm.get(ev.cid, 0)
                            if k_adm == 0:
                                flats[ev.cid] = self._flat_params
                                c = self.clients[ev.cid]
                                c.model_state = self.global_state
                                c.version = r
                            else:
                                force_global.add((ev.cid, k_adm))
                                resync_after.add(ev.cid)
                            continue
                        n_adm[ev.cid] = n_adm.get(ev.cid, 0) + 1
                        resync_after.discard(ev.cid)
                        stal.append(ev.staleness)
                        evfaults.append(ev.fault)
                        evcomp.append(ev.compute_s)
                        events.append((ev.time, ev.cid))
                    pop_span.set_metadata(popped=popped,
                                          admitted=len(events))
                if not events:
                    break
                now = t_pop
                kh = len(events)  # this horizon's admitted upload count
                sizes = [self.clients[cid].n_samples for _, cid in events]
                wh = betah = None
                pend: Dict[int, tuple] = {}
                next_fold = 0
                # defense factors per horizon slot (np.float32), filled
                # as each wave is screened; consumed by the in-order
                # streaming fold loop and the buffered server round alike
                hfac: Optional[Dict[int, np.float32]] = (
                    {} if self._defense != "none" else None)
                if self._streaming:
                    # discount-at-ingest weights for the whole horizon,
                    # slot-ordered (identical np kernels to the sequential
                    # path's per-upload singleton — bitwise the same folds)
                    wh = self._weight_vector(stal, sizes)
                    if cfg.aggregation == "fedasync":
                        betah = np.float32(1.0) - wh

                # ---- wave decomposition ----
                waves: List[List[Tuple[int, int]]] = []  # (slot, cid)
                n_events: Dict[int, int] = {}
                for slot, (_, cid) in enumerate(events):
                    w = n_events.get(cid, 0)
                    n_events[cid] = w + 1
                    if w == len(waves):
                        waves.append([])
                    waves[w].append((slot, cid))
                round_span.set_metadata(uploads=kh, waves=len(waves))

                g_flat, g_state = self._flat_params, self.global_state
                nbytes = self._upload_nbytes()
                prev_new_flat = prev_states = None
                # refresh result per client with further events this
                # horizon: None = adopted the round-r global model, int =
                # row index into the previous wave's outputs (continue
                # the local chain)
                carry: Dict[int, Optional[int]] = {}
                last_slot_state = None  # state of the event in slot K-1
                state_parts: List[Pytree] = []  # fedavg state mean
                size_parts: List[int] = []
                for w, members in enumerate(waves):
                    kw = len(members)
                    self.wave_size_hist[kw] = \
                        self.wave_size_hist.get(kw, 0) + 1
                    kb = self._wave_bucket(kw)
                    with _span("safl.wave", wave=w, lanes=kw, bucket=kb):
                        npad = kb - kw
                        # bucketing: padding lanes duplicate the first
                        # member's inputs (lanes are independent, so real
                        # lanes are untouched); their rows scatter to the
                        # dropped slot K and host bookkeeping iterates
                        # real members only
                        cids = [cid for _, cid in members] \
                            + [members[0][1]] * npad
                        with _span("safl.gather") as sp:
                            starts, states, stacked = self._gather_wave(
                                w, cids, force_global, carry, g_flat,
                                g_state, prev_new_flat, prev_states)
                            sp.set_metadata(stacked=stacked,
                                            leaves=state_leaves)
                        with _span("safl.train"):
                            vecs, new_flat, new_states, _losses = wave_fn(
                                starts, states, xs_all, ys_all, mask_all,
                                jnp.asarray(cids), cfg.client_lr, *self._fz)
                        with _span("safl.encode"):
                            prows = self._encode_wave(
                                vecs, members, cids, evfaults, hfac, use_ef)
                        with _span("safl.fold") as sp:
                            next_fold, folds, skipped = self._fold_wave(
                                prows, members, npad, pend, next_fold,
                                wh, betah, hfac, stal)
                            sp.set_metadata(folds=folds, skipped=skipped)

                        # ---- host bookkeeping + client refresh ----
                        with _span("safl.refresh") as sp:
                            sliced = 0
                            chains = []  # (cid, row): continuing clients
                            # model targets on the quantized channel: the
                            # server-side state view is the int8
                            # roundtrip (identity otherwise)
                            up_states = (self._state_q8_rows(new_states)
                                         if target == "params"
                                         else new_states)
                            prefix = cfg.aggregation == "fedavg" and npad
                            if cfg.aggregation == "fedavg":
                                state_parts.append(
                                    take_states(up_states, np.arange(kw))
                                    if prefix else up_states)
                            for row, (slot, cid) in enumerate(members):
                                c = self.clients[cid]
                                self.tx_bytes += nbytes
                                # staleness was recorded at pop time from
                                # the scheduler's projected versions
                                # (== r - c.version here: the projection
                                # mirrors this refresh rule)
                                size_parts.append(c.n_samples)
                                if (slot == kh - 1
                                        and cfg.aggregation != "fedavg"):
                                    # fedavg takes the weighted state
                                    # mean instead
                                    last_slot_state, = split_states(
                                        up_states, [row])
                                    sliced += 1
                                # refresh rule (paper §2.2.2): adopt the
                                # round-r global model iff one arrived
                                # since this client's version; else
                                # continue the local chain from w_end
                                adopt = c.version < r
                                c.version = r
                                if n_events[cid] > w + 1:  # more events
                                    carry[cid] = None if adopt else row
                                elif adopt:
                                    flats[cid] = g_flat
                                    c.model_state = g_state
                                else:
                                    chains.append((cid, row))
                            # the continuing clients' rows and states,
                            # all in one program
                            ends = split_states((new_flat, new_states),
                                                [row for _, row in chains])
                            for (cid, _), (flat, state) in zip(chains, ends):
                                flats[cid] = flat
                                self.clients[cid].model_state = state
                            sliced += len(chains)
                            prev_new_flat, prev_states = new_flat, new_states
                            if w == len(waves) - 1:
                                # reject/crash resets that landed after a
                                # client's last admitted lane: the client
                                # ends the horizon on the round-r global
                                # model, like the sequential oracle's
                                # inline reset
                                for cid in resync_after:
                                    flats[cid] = g_flat
                                    c = self.clients[cid]
                                    c.model_state = g_state
                                    c.version = r
                            sp.set_metadata(
                                sliced=sliced,
                                leaves=state_leaves if sliced or prefix
                                else 0)

                # ---- fused server round (no host sync) ----
                facs = ([hfac[i] for i in range(kh)]
                        if hfac is not None else None)
                with _span("safl.finalize"):
                    if self._streaming:
                        assert next_fold == kh, (next_fold, kh)
                        m = self._server_round_streaming(stal)
                    else:
                        m = self._server_round(stal, sizes, facs)
                t_open = self._last_agg_time
                self._last_agg_time = now
                self._global_stale = True
                if self.tracer is not None:
                    # per-slot values are identical to the sequential
                    # oracle's (same pop sequence, same host math); the
                    # tracer's sorted flush makes emission order
                    # irrelevant
                    for slot, (t_ev, cid) in enumerate(events):
                        self.tracer.upload(
                            slot=slot, cid=cid, t=t_ev,
                            compute_s=evcomp[slot],
                            comm_s=self.clients[cid].comm_time,
                            staleness=stal[slot], nbytes=nbytes,
                            wire=self._wire,
                            fac=None if hfac is None else hfac[slot])
                    self._trace_round(stal, sizes, facs, t_open, now)
                # device-resident sched stats: scatter-add this round's
                # staleness values + client ids (host ints in — the ring
                # pads them to a power of two so queue/timeout horizons
                # keep the writer at O(log K) compiles; donated in-place
                # writes, host transfer happens once, at the run-end
                # flush)
                ring.append_sched(stal, [cid for _, cid in events])
                with _span("safl.state"):
                    if cfg.aggregation == "fedavg":
                        stacked = (state_parts[0] if len(state_parts) == 1
                                   else jax.tree_util.tree_map(
                                       lambda *xs: jnp.concatenate(xs),
                                       *state_parts))
                        if jax.tree_util.tree_leaves(stacked):
                            self.global_state = agg.weighted_mean(
                                stacked,
                                jnp.asarray(size_parts, jnp.float32))
                    else:
                        self.global_state = last_slot_state

                # ---- eval_every-gated eval into the device metrics
                # ring ----
                with _span("safl.eval"):
                    rnd = self.t_global
                    if self._eval_due(rnd, n_rounds):
                        acc, loss, *report = eval_fn(self._flat_params,
                                                     self.global_state,
                                                     self.test_x,
                                                     self.test_y, *self._fz)
                        reports += report
                        ring.append(acc, loss, m["update_norm"],
                                    np.float32(self.screened_uploads),
                                    np.float32(self.clipped_uploads))
                        pending.append(dict(
                            round=rnd, sim_time=now + self._agg_overhead(),
                            tx_bytes=self.tx_bytes, rx_bytes=self.rx_bytes,
                            mean_staleness=float(np.mean(stal)),
                            max_staleness=int(max(stal))))
                        if log_every and rnd % log_every == 0:
                            # opt-in logging is the one place a fetch is
                            # allowed
                            print(f"  [SAFL-{cfg.aggregation}] round {rnd} "
                                  f"acc={float(acc):.4f} "
                                  f"loss={float(loss):.4f} "
                                  f"stale={np.mean(stal):.2f}")

        # ---- the ONE device->host metrics transfer of the run ----
        with _span("safl.flush"):
            got = (jax.device_get(reports) if reports
                   else [{}] * len(pending))
            for fields, (acc, loss, unorm, nscr, nclip), rep in zip(
                    pending, ring.flush(), got):
                self.metrics.record(
                    reported=_floats(rep),
                    accuracy=float(acc), loss=float(loss),
                    nan_event=not np.isfinite(loss),
                    update_norm=float(unorm),
                    screened_uploads=int(nscr), clipped_uploads=int(nclip),
                    **fields)
            hist, part = ring.flush_sched()
            self._dev_stale_hist += hist.astype(np.int64)
            self._dev_participation += part.astype(np.int64)

    def _gather_wave(self, w: int, cids: List[int], force_global: set,
                     carry: Dict[int, Optional[int]], g_flat: jax.Array,
                     g_state: Pytree, prev_new_flat: Optional[jax.Array],
                     prev_states: Optional[Pytree]) -> tuple:
        """A wave's start rows and per-client model states, one lane per
        entry of ``cids`` (padding lanes included).  Returns ``(starts,
        states, stacked)``; ``stacked`` counts the lanes assembled from
        per-client arrays (0 when the wave starts from one broadcast or
        gathered array)."""
        kb = len(cids)
        if w == 0:
            starts, states = stack_states(
                [(self._client_flats[cid], self.clients[cid].model_state)
                 for cid in cids])
            return starts, states, kb
        # a force_global lane restarts from the round-r global model (a
        # reject/crash landed between this client's admitted events) —
        # same row/state source as an adopting lane, so it reuses the
        # None path
        rows = [None if (cid, w) in force_global else carry.get(cid)
                for cid in cids]
        if all(rv is None for rv in rows):
            # common case: every wave-0 member adopted the round-r global
            # model
            starts, states = broadcast_states((g_flat, g_state), kb)
            return starts, states, 0
        prev = (prev_new_flat, prev_states)
        if all(rv is not None for rv in rows):
            starts, states = take_states(prev, rows)
            return starts, states, 0
        # mixed: force_global lanes next to continuing local chains
        # (mid-horizon crashes), or a future schedule the refresh rule
        # doesn't cover
        carried = iter(split_states(prev, [rv for rv in rows
                                           if rv is not None]))
        starts, states = stack_states(
            [(g_flat, g_state) if rv is None else next(carried)
             for rv in rows])
        return starts, states, kb

    def _encode_wave(self, vecs: jax.Array, members: List[Tuple[int, int]],
                     cids: List[int], evfaults: List,
                     hfac: Optional[Dict[int, np.float32]],
                     use_ef: bool) -> tuple:
        """Serialize a wave into the server channel's wire format: the
        stacked payload arrays ((vecs,) on f32, (q, s) on q8/q4, (idx,
        qv, s) on topk), with error-feedback residuals written back,
        payload faults applied and the defense screen's factors stored
        in ``hfac`` by horizon slot."""
        cfg = self.cfg
        kw, kb = len(members), len(cids)
        npad = kb - kw
        new_res = None
        if use_ef:
            # padding lanes read member 0's pre-update residual (their
            # outputs are discarded below)
            res = jnp.stack([self._residual(cid) for cid in cids])
        if self._quant:
            if use_ef:
                q, s, new_res = self.codec.quantize_rows(vecs, res)
            else:
                q, s = self.codec.quantize_rows_nores(vecs)
            prows = (q, s)
        elif self._q4:
            # per-lane (cid, counter) PRNG keys; real lanes consume their
            # client's next counter, padding lanes repeat lane 0's key
            # (rows dropped either way)
            ctrs = [self._next_counter(cid) for cid in cids[:kw]]
            ctrs += [ctrs[0]] * npad
            cids_v = jnp.asarray(cids, jnp.int32)
            ctrs_v = jnp.asarray(ctrs, jnp.int32)
            if use_ef:
                q, s, new_res = self.codec.quantize_rows_q4(
                    vecs, res, cfg.seed, cids_v, ctrs_v)
            else:
                q, s = self.codec.quantize_rows_q4_nores(
                    vecs, cfg.seed, cids_v, ctrs_v)
            prows = (q, s)
        elif self._topk:
            if use_ef:
                ti, tq, ts, new_res = self.codec.quantize_rows_topk(vecs,
                                                                    res)
            else:
                ti, tq, ts = self.codec.quantize_rows_topk_nores(vecs)
            prows = (ti, tq, ts)
        else:
            prows = (vecs,)
        if new_res is not None:
            for row, cid in enumerate(cids[:kw]):
                self._residuals[cid] = new_res[row]
        # wire-level faults land on the serialized rows (the residuals
        # above were already updated against the clean payload — the
        # client believes it sent a good row); padding lanes carry no
        # fault, and the appliers leave unfaulted lanes bitwise untouched
        wfaults = [evfaults[slot] for slot, _ in members] + [None] * npad
        prows = self._apply_payload_faults(prows, wfaults)
        if hfac is not None:
            # defense screen: one fused per-row pass over the wave
            # (padding lanes screened but never counted); verdicts are
            # keyed by horizon slot so the streaming fold consumes them
            # in arrival order, exactly like the sequential path
            fac = self._screen_factors(prows, kw)
            for row, (slot, _cid) in enumerate(members):
                hfac[slot] = fac[row]
            if not self._streaming and bool((fac == np.float32(0.0)).any()):
                mask = np.zeros(kb, bool)
                mask[:kw] = fac == np.float32(0.0)
                prows = self._zero_screened_rows(prows, mask)
        return prows

    def _fold_wave(self, prows: tuple, members: List[Tuple[int, int]],
                   npad: int, pend: Dict[int, tuple], next_fold: int,
                   wh, betah, hfac: Optional[Dict[int, np.float32]],
                   stal: List[int]) -> Tuple[int, int, int]:
        """Put a wave's payload rows into the server channel.  Returns
        ``(next_fold, folds, skipped)``: the next horizon slot to fold
        and the uploads this wave folded or skipped (both 0 on the
        buffered channel, which writes the rows into its (K, D) buffer).
        """
        if not self._streaming:
            # padding lanes get the first out-of-range slot: dropped by
            # the scatter (write_rows mode="drop")
            slots = np.asarray([slot for slot, _ in members]
                               + [self._horizon_target] * npad, np.int32)
            if self._quant or self._q4:
                self._qbuf.write_rows(*prows, slots)
            elif self._topk:
                self._tbuf.write_rows(*prows, slots)
            else:
                self._buf = flatbuf.write_rows(self._buf, prows[0],
                                               jnp.asarray(slots))
            return next_fold, 0, 0
        # hold-and-release: waves surface rows out of arrival order (wave
        # 0 spans the whole horizon), but the sequential oracle folds in
        # arrival order — so rows park in ``pend`` and fold strictly in
        # slot order, which makes the batched fold chain the sequential
        # one by construction (and keeps fedasync's non-commuting mix
        # exact)
        folds = skipped = 0
        for row, (slot, _cid) in enumerate(members):
            pend[slot] = tuple(a[row] for a in prows)
        while next_fold in pend:
            payload = pend.pop(next_fold)
            fw = wh[next_fold]
            if hfac is not None:
                fv = hfac[next_fold]
                if fv == np.float32(0.0):
                    # screened: the fold is skipped outright (0 x NaN is
                    # NaN) — skip() records the arrival with an exact 0.0
                    # weight
                    self._accum.skip(shard=self._fold_shard(next_fold),
                                     staleness=stal[next_fold])
                    next_fold += 1
                    skipped += 1
                    continue
                fw = np.float32(fw * fv)
            self._accum.fold(
                payload, w=fw,
                beta=(np.float32(1.0) - fw if betah is not None else 1.0),
                shard=self._fold_shard(next_fold),
                staleness=stal[next_fold])
            next_fold += 1
            folds += 1
        return next_fold, folds, skipped

    # ---------- crash-consistent engine snapshots (PR 8) ----------

    def _snapshot_tree(self) -> Dict:
        """The snapshot's array pytree: the global flat row, server opt
        state, the non-trainable global state, per-client EF residuals
        and each client's carried model (flat rows on the batched path,
        param pytrees on the sequential one).  Dict keys are strings so
        the flatten order is reproducible at load time."""
        tree: Dict[str, Any] = {
            "flat_params": self._flat_params,
            "opt": self._opt,
            "global_state": self.global_state,
            "residuals": {str(k): v for k, v in self._residuals.items()},
            "client_state": {str(c.cid): c.model_state
                             for c in self.clients},
        }
        if self.cfg.batch_clients:
            flats = (self._client_flats
                     or [self._flat_params] * len(self.clients))
            tree["client_rows"] = {str(c.cid): flats[c.cid]
                                   for c in self.clients}
        else:
            tree["client_params"] = {str(c.cid): c.params
                                     for c in self.clients}
        return tree

    def save_snapshot(self, ckpt_dir: str, keep: int = 3) -> int:
        """Crash-consistent snapshot of the SAFL engine at a run()
        boundary (between incremental ``run()`` calls the event heap,
        client chains and channel are all quiescent — the channel buffer
        is empty and the streaming accumulator sealed).  Arrays go
        through :func:`repro.checkpoint.io.save_checkpoint`; the host
        state (simulated clocks, PRNG/fault counters, the event heap,
        accounting and metric records) lands in an atomically-renamed
        ``engine_{step}.json`` sidecar.  The sidecar is written FIRST and
        the checkpoint's own json last — the commit record
        ``latest_step`` keys on — so a kill between the two leaves no
        resumable-looking step behind.  Resuming from the snapshot
        replays the uninterrupted run bit-exactly."""
        assert self.cfg.mode == "semi_async", \
            "snapshots cover the SAFL engines"
        step = int(self.t_global)
        state = {
            "t_global": step,
            "batched": bool(self.cfg.batch_clients),
            "last_agg_time": float(self._last_agg_time),
            "tx_bytes": int(self.tx_bytes),
            "rx_bytes": int(self.rx_bytes),
            "idle_time": float(self.idle_time),
            "last_update_norm": float(self._last_update_norm),
            "staleness_hist": {str(k): int(v)
                               for k, v in self.staleness_hist.items()},
            "sr_counter": {str(k): int(v)
                           for k, v in self._sr_counter.items()},
            "residual_cids": sorted(self._residuals),
            "client_versions": [int(c.version) for c in self.clients],
            "screened_uploads": self.screened_uploads,
            "clipped_uploads": self.clipped_uploads,
            "corrupted_uploads": self.corrupted_uploads,
            "byzantine_uploads": self.byzantine_uploads,
            "dev_stale_hist": self._dev_stale_hist.tolist(),
            "dev_participation": self._dev_participation.tolist(),
            "sched": self.sched.state(),
            "metrics": [dataclasses.asdict(rec)
                        for rec in self.metrics.records],
        }
        ckptio.save_state_json(ckpt_dir, step, state)
        ckptio.save_checkpoint(ckpt_dir, step, self._snapshot_tree(),
                               keep=keep)
        return step

    def load_snapshot(self, ckpt_dir: str,
                      step: Optional[int] = None) -> int:
        """Restore a :meth:`save_snapshot` state into this (freshly
        constructed, identically configured) engine.  The array template
        is rebuilt from the engine's own structures plus the sidecar's
        key sets (which clients own EF residuals), so shapes and dtypes
        are validated leaf by leaf."""
        if step is None:
            step = ckptio.latest_step(ckpt_dir)
            if step is None:
                raise FileNotFoundError(f"no snapshots in {ckpt_dir}")
        state = ckptio.load_state_json(ckpt_dir, step)
        assert state["batched"] == bool(self.cfg.batch_clients), \
            "snapshot was taken on the other engine path"
        tpl: Dict[str, Any] = {
            "flat_params": self._flat_params,
            "opt": self._opt,
            "global_state": self.global_state,
            "residuals": {str(cid): self.codec.zero_residual()
                          for cid in state["residual_cids"]},
            "client_state": {str(c.cid): c.model_state
                             for c in self.clients},
        }
        if state["batched"]:
            tpl["client_rows"] = {str(c.cid): self._flat_params
                                  for c in self.clients}
        else:
            tpl["client_params"] = {str(c.cid): c.params
                                    for c in self.clients}
        tree, _ = ckptio.load_checkpoint(ckpt_dir, tpl, step=step)
        self._flat_params = tree["flat_params"]
        self._opt = tree["opt"]
        self.global_state = tree["global_state"]
        self.global_params = self.codec.unravel(self._flat_params)
        self._global_stale = False
        self._residuals = {int(k): v
                           for k, v in tree["residuals"].items()}
        for c in self.clients:
            c.model_state = tree["client_state"][str(c.cid)]
            c.version = int(state["client_versions"][c.cid])
        if state["batched"]:
            self._client_flats = [tree["client_rows"][str(c.cid)]
                                  for c in self.clients]
        else:
            for c in self.clients:
                c.params = tree["client_params"][str(c.cid)]
        self.t_global = int(state["t_global"])
        self._last_agg_time = float(state["last_agg_time"])
        self.tx_bytes = int(state["tx_bytes"])
        self.rx_bytes = int(state["rx_bytes"])
        self.idle_time = float(state["idle_time"])
        self._last_update_norm = float(state["last_update_norm"])
        self.staleness_hist = {
            int(k): int(v) for k, v in state["staleness_hist"].items()}
        self._sr_counter = {
            int(k): int(v) for k, v in state["sr_counter"].items()}
        self.screened_uploads = int(state["screened_uploads"])
        self.clipped_uploads = int(state["clipped_uploads"])
        self.corrupted_uploads = int(state["corrupted_uploads"])
        self.byzantine_uploads = int(state["byzantine_uploads"])
        self._dev_stale_hist = np.asarray(state["dev_stale_hist"],
                                          np.int64)
        self._dev_participation = np.asarray(state["dev_participation"],
                                             np.int64)
        self.sched.load_state(state["sched"])
        self.metrics.records = [RoundRecord(**rec)
                                for rec in state["metrics"]]
        return step
