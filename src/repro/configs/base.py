"""Config system for the SAFL reproduction framework.

Two config families:

* :class:`ModelConfig` — architecture description for the assigned big-model
  zoo (dense / MoE / SSM / hybrid / enc-dec audio / VLM).  Every assigned
  architecture in ``src/repro/configs/<id>.py`` instantiates one of these with
  the exact dimensions from the assignment table (source cited per file).
* :class:`FLConfig` — the paper's federated-learning experiment description
  (clients, K, sync vs semi-async, aggregation target, data distribution).

Shape/table constants for the four assigned input shapes live in
:data:`INPUT_SHAPES`.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture description.

    ``family`` selects the block stack:
      dense   — pre-norm decoder (GQA attention + gated MLP)
      moe     — dense attention + mixture-of-experts MLP (dense dispatch)
      ssm     — xLSTM (alternating mLSTM / sLSTM blocks)
      hybrid  — Mamba2 backbone with a shared attention block every Nth layer
      audio   — encoder-decoder; encoder consumes precomputed frame embeddings
      vlm     — decoder LM consuming a precomputed patch-embedding prefix
    """

    name: str
    family: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // n_heads

    # --- attention ---
    rope_theta: float = 10_000.0
    qk_norm: bool = False
    sliding_window: Optional[int] = None  # native window (starcoder2)
    long_context_window: int = 8_192  # window used for long_500k decode
    attn_chunk: int = 0  # 0 -> naive full-matrix attention; >0 -> q-chunked
    attn_impl: str = "chunked"  # chunked | online (flash-style, §Perf)
    attn_kv_chunk: int = 1_024  # kv tile for attn_impl="online"
    # multi-head latent attention (DeepSeek-V2/V3): attn_type="mla"
    # projects a kv_lora_rank latent (RMS-normed), expands it per head
    # to qk_nope_head_dim keys and v_head_dim values, and adds one
    # qk_rope_head_dim rope key shared by every head (no q latent)
    attn_type: str = "gqa"  # gqa | mla
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    moe_group_size: int = 1_024
    first_k_dense: int = 0  # leading dense layers before the MoE stack
    d_ff_dense: int = 0  # width of those dense layers (0 -> d_ff)
    # router: "softmax" (top-k of softmax, capacity-dropped, aux loss) |
    # "sigmoid" (DeepSeek-V3 noaux_tc: top-k of sigmoid scores plus a
    # selection-only bias, chosen scores normalised and scaled by
    # routed_scaling_factor, no capacity, nothing dropped)
    router: str = "softmax"
    routed_scaling_factor: float = 1.0
    # expert parallelism: the layer routes over all n_experts and holds
    # (computes) only experts [held_expert_offset, + n_experts_held);
    # 0 holds every expert
    n_experts_held: int = 0
    held_expert_offset: int = 0
    moe_dispatch_dtype: str = "float32"  # bf16 halves dispatch traffic
    moe_dispatch_impl: str = "einsum"  # einsum (GShard) | scatter (§Perf)

    # --- SSM / hybrid (Mamba2) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_chunk: int = 256
    hybrid_attn_every: int = 0  # zamba2: shared attn block every Nth layer

    # --- xLSTM ---
    block_pattern: Tuple[str, ...] = ()  # e.g. ("mlstm", "slstm")

    # --- encoder-decoder ---
    enc_layers: int = 0

    # --- modality frontend stub ---
    n_prefix_tokens: int = 0  # VLM patches / share of seq given to prefix

    # --- numerics ---
    act: str = "swiglu"  # swiglu | gelu
    norm_eps: float = 1e-5
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    tie_embeddings: bool = False
    vocab_pad_to: int = 2_048

    # --- distribution / training policy ---
    sharding: str = "megatron"  # megatron | fsdp
    optimizer: str = "sgdm"  # sgd | sgdm | adamw
    remat: bool = True
    scan_layers: bool = True
    source: str = ""  # citation for the assignment row
    # low-rank adapters y = x W + (lora_alpha / lora_rank) (x A) B on the
    # attention projections named in lora_targets (0 = none)
    lora_rank: int = 0
    lora_alpha: float = 0.0
    lora_targets: Tuple[str, ...] = ()

    # ------------------------------------------------------------------
    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def dense_ff(self) -> int:
        """Width of a dense MLP layer (the experts' is ``d_ff``)."""
        return self.d_ff_dense or self.d_ff

    @property
    def held_experts(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def padded_vocab(self) -> int:
        return round_up(self.vocab_size, self.vocab_pad_to)

    @property
    def d_inner(self) -> int:  # mamba2 inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def is_encoder_decoder(self) -> bool:
        return self.family == "audio"

    @property
    def supports_long_decode(self) -> bool:
        """long_500k policy (see DESIGN.md §4).

        SSM/hybrid decode is O(1)-state; dense/MoE/VLM decoders run the
        sliding-window variant; the enc-dec speech model has no 500k-token
        autoregressive mode and is skipped.
        """
        return self.family != "audio"

    def validate(self) -> None:
        assert self.family in ("dense", "moe", "ssm", "hybrid", "audio", "vlm")
        if self.family != "ssm":
            assert self.d_model % self.n_heads == 0 or self.head_dim
            assert self.n_heads % self.n_kv_heads == 0
        if self.family == "moe":
            assert self.n_experts > 0 and self.top_k > 0
            assert self.router in ("softmax", "sigmoid"), self.router
            assert (0 <= self.held_expert_offset and self.held_expert_offset
                    + self.held_experts <= self.n_experts)
            if self.router == "softmax":  # capacity dispatch holds all
                assert self.held_experts == self.n_experts
        assert self.attn_type in ("gqa", "mla"), self.attn_type
        if self.attn_type == "mla":
            assert (self.kv_lora_rank and self.qk_nope_head_dim
                    and self.qk_rope_head_dim and self.v_head_dim)
        if self.family == "ssm":
            assert self.block_pattern, "ssm family needs a block pattern"
        if self.family == "hybrid":
            assert self.hybrid_attn_every > 0
            assert self.n_layers % self.hybrid_attn_every == 0


# ---------------------------------------------------------------------------
# Federated-learning configuration (the paper's experiment axis)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FLConfig:
    """One SAFL/SFL experiment (paper §2, §4).

    Server backend: the flat-buffer server round
    (:class:`repro.core.aggregation.FlatServer`) auto-detects its backend —
    compiled Pallas kernels on TPU, the jnp oracle on CPU — and honours the
    ``REPRO_AGG_BACKEND=pallas|pallas_interpret|xla`` environment override
    (``pallas_interpret`` routes the kernel bodies through the Pallas
    interpreter for validation).

    Wire formats (``wire``; ``compress_updates=True`` is the legacy alias
    for ``wire="q8"``): what one upload puts on the channel, per coord of
    the ``quant_block``-padded flat dimension Dq (d raw coords):

    ======  ==================  =============  ==========================
    wire    bytes/upload        err. feedback  fused server entry points
    ======  ==================  =============  ==========================
    f32     4d                  none (exact)   ``safl_aggregate`` /
                                               ``safl_fold``
    q8      Dq + 4Dq/B          residual       ``safl_aggregate_q8`` /
            (~4x)               (grad tgts)    ``safl_fold_q8``
    q4      Dq/2 + 4Dq/B        residual +     ``safl_aggregate_q4`` /
            (~8x)               stoch. round   ``safl_fold_q4``
    topk    5nk + 4nk/B         residual incl. none: XLA scatter of
            (~8x @ 10%)         dropped coords ``kernels.ref`` on every
                                               backend
    ======  ==================  =============  ==========================

    (B = ``quant_block``; nk = ``ceil(topk_frac * d)`` rounded up to a
    B multiple.)  ``q8``: int8 rows, one f32 absmax scale per B lanes,
    server fuses the dequantize into the aggregation.  ``q4``: two int4
    lanes per byte on the [-7, 7] grid with *stochastic rounding* — the
    uniform draws are keyed per (client, upload counter) from the jax
    PRNG (the ``sched.timing`` jitter rule), so the sequential and
    batched engine paths quantize bit-identically; the rounding is
    unbiased, so the error-feedback residual telescopes.  ``topk``: only
    the nk largest-|coordinate| entries travel, as (int32 index, int8
    value) pairs; the residual carries the dropped coordinates in full,
    and the server aggregates through an XLA
    gather-dequant-scatter-accumulate without materializing dense rows.
    ``topk`` is *gradient-only*: fedavg / fedasync upload weights, and a
    sparse weight average would zero untransmitted coordinates.

    Gradient-target uploads keep a client-side error-feedback residual
    (``error_feedback``) so the quantization noise telescopes across
    rounds instead of accumulating; model-target uploads (fedavg /
    fedasync) quantize the weights themselves (no residual — weights do
    not accumulate).  Transmitted bytes are accounted at the wire payload
    size (:func:`repro.kernels.quantize.payload_nbytes` + envelope) for
    every aggregation target, including the fedavg/fedasync non-trainable
    BN-state payload (shipped through the ravel_q8 wire format on every
    lossy wire).

    Multi-device mesh / topology knobs (tentpole PR 9 adds the 2-D
    hierarchical mesh — clients -> edge aggregators -> server):

    ============  =====================================================
    knob          effect
    ============  =====================================================
    devices       1-D mesh: flat channel rows + wave lanes over P "pod"
                  shards; server reduce = per-shard partials + ONE
                  global psum.  Alias for ``mesh_shape=(1, P)``.
    mesh_shape    (E, P) 2-D (edge, pod) mesh: rows/lanes lay over the
                  *flattened* E*P axis, per-shard partials tree-reduce
                  within their edge group (log2(P) ppermute rounds,
                  f32 partials — q8/q4 dequantize first), then ONE
                  cross-edge psum of E edge partials reaches the server
                  step.  Cross-edge traffic drops ~P x vs the flat
                  psum.  P must be a power of two; K (and a queue
                  horizon) must divide E*P.  (1, P) is bit-exact vs
                  ``devices=P``; set at most one of the two knobs to
                  > 1 device.
    wave_impl     wave lane execution: vmap / lax.map / auto (per
                  model+backend) — orthogonal to the mesh; lanes pin to
                  the flattened row axis either way.
    wave_buckets  pow2-bucket wave sizes (masked lanes) so high-churn
                  schedules compile O(log k) wave programs per mesh —
                  one program per (mode, wire, wave bucket), guarded by
                  the engine's compile-count diagnostics.
    server_.....  ``server_channel="streaming"`` composes with both
    channel       meshes: the accumulator bank keeps one row per mesh
                  shard (per-edge partial sums on the 2-D mesh —
                  fold-at-edge; finalize = intra-edge tree reduce +
                  cross-edge psum).
    ============  =====================================================

    Streaming server channel (``server_channel``, tentpole PR 6): the
    semi-async engine defaults to accumulate-on-arrival aggregation —
    each upload is folded into a double-buffered O(D) accumulator bank
    (:class:`repro.core.flatbuf.AccumBuffer`) the moment it lands, with
    its FINAL aggregation weight composed at ingest (staleness discount /
    data size / policy score / fedasync mix rate), so peak channel memory
    is independent of how many uploads a horizon admits.  ``"buffered"``
    keeps the resident (K, D) row buffer — the bit-exact parity oracle
    (f32; q8 within the established tolerance) — and ``"auto"`` picks
    streaming for semi-async, buffered for sync (the batched SFL round
    emits whole (K, D) blocks).  The streaming fold honours the same
    ``REPRO_AGG_BACKEND`` override as the buffered step: the Pallas
    ``safl_fold``/``safl_fold_q8`` kernels on TPU (or
    ``pallas_interpret``), the jnp fold oracle on CPU — backend choice
    never changes which channel runs.

    Aggregation horizons (``horizon``): ``"k"`` closes a horizon after
    exactly ``k`` admitted uploads (the paper's buffered-K rule);
    ``"queue"`` after ``horizon_queue`` uploads (0 -> ``k``; with the
    buffered channel this doubles as the queue-length parity oracle);
    ``"timeout"`` at the first upload once ``horizon_timeout_s``
    simulated seconds have passed since the last aggregation (SEAFL-style
    adaptive horizons, arXiv:2503.05755 — admits an unbounded number of
    uploads, so it requires the streaming channel); ``"hybrid"``
    whichever of queue/timeout fires first.

    Rate control (``sched_policy="ratelimit"``): a FedBuff-style server
    that asks fast clients to IDLE once ``sched_rate_limit`` uploads have
    been admitted in the current round — idle clients skip the upload
    (no buffer slot, no tx bytes) and retrain from the current global
    model; the run summary counts ``idle_requests`` next to the
    rejected/no-show counters.

    Fault injection + server defense (``fault_*`` / ``defense``,
    tentpole PR 8): a :class:`repro.faults.FaultPlan` draws one fault
    per (client, upload attempt), keyed per (cid, upload counter) from
    the jax PRNG exactly like the q4 stochastic rounding, so the
    sequential and batched engines replay bit-identical chaos:

    ==========  ============================  =========================
    knob        fault                         defense that catches it
    ==========  ============================  =========================
    fault_      upload lost + client reboot:  none needed — the sched
    crash_p     progress discarded, WAKE      re-enqueues with backoff
                re-enqueued after
                ``fault_retry_backoff_s *
                2^min(streak,cap)-1``
    fault_      next compute period runs      staleness discount /
    straggler_p ``fault_straggler_mult`` x    seafl cap (existing)
                slower
    fault_      NaN/Inf lanes (f32), XOR      ``defense=screen``:
    corrupt_p   bit-flips + Inf scale block   non-finite row sums get
                (q8/q4/topk)                  weight 0
    fault_      row (f32) or scales (quant)   ``defense=screen|clip``
    byzantine_p x ``-fault_byzantine_         with ``defense_norm_cap``
                rescale``                     > 0 (norm screen / clip)
    ==========  ============================  =========================

    ``defense`` runs a fused per-row screening pass (sum of squares of
    the dequantized row — Pallas kernel on TPU, jnp oracle on CPU) on
    every upload; verdicts ride the ``external_discount`` weight path:
    ``screen`` zeroes a screened row's aggregation weight (the buffered
    channel also zeroes its payload; the streaming channel skips the
    fold — a folded row cannot be un-folded), ``clip`` down-weights
    finite rows to ``defense_norm_cap / norm`` influence.  Screened /
    clipped counts land in the device metrics ring and the run summary.
    Engine snapshots (``FLEngine.save_snapshot`` / ``load_snapshot``,
    ``fl_sim --ckpt-dir/--ckpt-every/--resume``) capture the full
    engine + sched + fault state between aggregation rounds;
    kill-and-resume replays the uninterrupted run bit-exactly.

    Observability (``trace_*``, tentpole PR 10): a host-side structured
    tracing layer (:mod:`repro.obs`) records per-upload lifecycle spans
    and per-horizon round spans on the *simulated* clock.  Tracing off
    is the default and is bit-exact with the untraced engine (no tracer
    is even constructed); tracing on adds only host bookkeeping, and
    the sequential and batched paths emit identical span streams (the
    seq-vs-batched parity discipline extends to the trace):

    ===========  =====================================================
    knob         effect
    ===========  =====================================================
    trace_level  ``"off"`` (default — zero overhead); ``"round"``
                 (per-horizon round + aggregate spans only);
                 ``"upload"`` (full lifecycle: train span, wire
                 transfer span with payload bytes, server ingest
                 instant with staleness / defense factor / final
                 aggregation weight, plus scheduler reject / idle /
                 crash-backoff / wake / offline instants)
    trace_dir    directory for the JSONL span log (``trace.jsonl``);
                 empty keeps records in memory only
                 (``engine.tracer.records``).  ``fl_sim --trace-dir``
                 additionally exports Chrome-trace JSON
                 (``trace.json``, loadable in Perfetto /
                 chrome://tracing) and Prometheus-text + JSON metrics
                 snapshots; ``python -m repro.obs.report`` renders the
                 JSONL as an ASCII timeline
    ===========  =====================================================
    """

    n_clients: int = 50
    k: int = 10  # aggregation buffer size / activation count
    # aggregation horizon trigger (semi-async): "k" (the paper's
    # buffered-K rule), "queue" (horizon_queue admitted uploads, 0 -> k),
    # "timeout" (first upload after horizon_timeout_s simulated seconds
    # since the last aggregation; unbounded count -> streaming channel
    # required), "hybrid" (queue OR timeout, whichever first)
    horizon: str = "k"
    horizon_queue: int = 0  # queue/hybrid: uploads per horizon (0 -> k)
    horizon_timeout_s: float = 0.0  # timeout/hybrid: horizon wall-clock
    # server channel: "auto" (streaming for semi_async, buffered for
    # sync), "streaming" (O(D) accumulate-on-arrival AccumBuffer),
    # "buffered" (resident (K, D) rows — the bit-exact parity oracle)
    server_channel: str = "auto"
    mode: str = "semi_async"  # "sync" | "semi_async"
    aggregation: str = "fedsgd"  # fedsgd | fedavg | sdga | fedasync | fedbuff | fedopt
    local_epochs: int = 1
    local_batch_size: int = 32
    client_lr: float = 0.05
    server_lr: float = 1.0  # eta in Eq. (5)
    # SDGA / staleness-aware knobs
    staleness_alpha: float = 0.5  # polynomial discount (1+tau)^-alpha
    server_momentum: float = 0.0
    ema_anchor: float = 0.0  # pull toward running param average (SDGA)
    fedasync_alpha: float = 0.6
    # discrete-event time model (lognormal per-client speeds)
    speed_sigma: float = 0.6
    comm_mean_s: float = 1.0
    seed: int = 0
    # ---- client scheduling subsystem (repro.sched, tentpole PR 5) ----
    # device-time model for the semi-async event schedule (and the SFL
    # round durations): "static" (the original deterministic per-client
    # duration — the parity oracle), "lognormal" (heavy-tailed per-epoch
    # compute jitter exp(sigma * z), jax-PRNG seeded via sched_seed), or
    # "markov" (two-state availability: clients drop offline after an
    # upload with prob sched_drop_p for an Exponential(sched_off_mean_s)
    # holding time — no-show events — on top of the lognormal jitter).
    sched_timing: str = "static"
    sched_jitter_sigma: float = 0.25  # lognormal/markov per-epoch sigma
    sched_drop_p: float = 0.1  # markov: P(offline) after each upload
    sched_off_mean_s: float = 5.0  # markov: mean offline holding time
    # participation policy: "full" (every upload admitted — the paper's
    # implicit setting), "uniform" (C-of-N sampling per round, C =
    # sched_c; C = N is exactly full), "seafl" (selective training: skip
    # clients whose projected staleness exceeds sched_stale_cap — they
    # discard stale work and resync), "fedqs" (adaptive: admit all,
    # reweight aggregation coefficients by n_i/(1+tau_i)^sched_qs_beta).
    # See repro/sched/__init__.py for the source-paper mapping.
    sched_policy: str = "full"
    sched_c: int = 0  # uniform: clients admitted per round (0 -> n_clients)
    sched_stale_cap: int = 4  # seafl: max admissible projected staleness
    sched_qs_beta: float = 1.0  # fedqs: staleness exponent in the score
    # FedBuff-style rate control (sched_policy="ratelimit"): admit the
    # first sched_rate_limit uploads of each aggregation round, ask later
    # arrivals to idle (counted separately from rejections; 0 -> k)
    sched_rate_limit: int = 0
    sched_seed: int = 0  # PRNG seed for timing jitter + policy sampling
    # beyond-paper: lossy wire formats for the flat channel (see the
    # class docstring table; repro.kernels.quantize is the quantizer
    # home).  "f32" | "q8" | "q4" | "topk"; compress_updates=True is the
    # legacy alias for wire="q8" (kept for older configs/sweeps).
    wire: str = "f32"
    topk_frac: float = 0.1  # topk wire: fraction of coords kept
    compress_updates: bool = False
    quant_block: int = 512  # lanes per f32 absmax scale (wire granule)
    error_feedback: bool = True  # client-side residual on gradient targets
    # engine execution policy (tentpole PR 3): the semi-async engine runs
    # each aggregation horizon's K buffered local trainings as ONE vmapped
    # XLA program over heterogeneous per-client flat param rows instead of
    # K sequential dispatches, and defers metric scalars to a
    # device-resident ring flushed at run end.  batch_clients=False forces
    # the sequential per-upload path (the parity oracle).
    batch_clients: bool = True
    # multi-device SAFL (tentpole PR 4): devices > 1 lays the flat (K, D)
    # upload channel and the batched waves out over a 1-D mesh "pod" axis
    # (repro.sharding.flat) — wave training runs data-parallel across
    # devices and the server round becomes per-shard partial reductions +
    # one psum.  Requires devices <= jax.device_count() (on CPU hosts grow
    # the pool with XLA_FLAGS=--xla_force_host_platform_device_count=N
    # before the first jax import) and k % devices == 0 (shard_map splits
    # the K rows evenly).
    devices: int = 1
    # hierarchical 2-D (edge, pod) mesh (tentpole PR 9): (E, P) lays the
    # flat channel rows and wave lanes over the flattened E*P axis;
    # per-shard partials tree-reduce within their edge group before ONE
    # cross-edge psum (see the knob table above).  None -> the 1-D
    # ``devices`` mesh; (1, P) is the bit-exact ``devices=P`` alias.
    mesh_shape: Optional[Tuple[int, int]] = None
    # wave lane execution: "vmap" (one vectorized program — the parallel
    # hardware fast path), "map" (lax.map: one dispatch, lanes serial —
    # identical numerics, sidesteps the grouped-convolution lowering that
    # costs conv models 0.4-0.6x on CPU), or "auto" (map for conv models
    # on CPU, vmap everywhere else).
    wave_impl: str = "auto"
    # pad each wave to the next power-of-two size with masked rows (their
    # buffer slot is out of range, so the scatter drops them) — bounds
    # compilation to O(log k) distinct wave programs under high-churn
    # schedules instead of one per distinct wave size.  Numerics are
    # unchanged: lanes are independent, padding lanes are discarded.
    wave_buckets: bool = True
    # evaluate (and record a metrics row for) every eval_every-th
    # aggregation round; the final round is always evaluated.  1 = every
    # round (the paper's per-round curves).
    eval_every: int = 1
    # ---- fault injection + server defense (tentpole PR 8) ----
    # per-upload fault probabilities (priority: crash > straggler >
    # corrupt > byzantine; the first that fires wins the draw).  All
    # zero -> no FaultPlan is built and the engine is bit-identical to
    # a faultless build.  Semi-async only (faults ride the event heap).
    fault_crash_p: float = 0.0
    fault_straggler_p: float = 0.0
    fault_straggler_mult: float = 8.0  # compute spike on the next period
    fault_corrupt_p: float = 0.0
    fault_byzantine_p: float = 0.0
    fault_byzantine_rescale: float = 10.0  # row/scales x -rescale
    fault_seed: int = 7  # offsets the fault stream from SR/timing draws
    # crash retry: WAKE re-enqueued after backoff_s * 2^(streak-1),
    # exponent capped at fault_retry_cap (bounded backoff, so the
    # one-pending-event-per-client heap invariant always holds)
    fault_retry_backoff_s: float = 1.0
    fault_retry_cap: int = 5
    # server-side defense: "none" | "screen" (zero the aggregation
    # weight of rows whose screening sum is non-finite, or whose L2
    # norm exceeds defense_norm_cap when > 0) | "clip" (drop non-finite
    # rows, down-weight finite rows to defense_norm_cap/norm influence
    # — requires defense_norm_cap > 0)
    defense: str = "none"
    defense_norm_cap: float = 0.0  # 0 -> isfinite screening only
    # ---- observability (tentpole PR 10, see the trace_* table in the
    # class docstring and repro/obs/README.md) ----
    trace_level: str = "off"  # off | round | upload
    trace_dir: str = ""  # JSONL span log directory ("" = in-memory only)
    # metrics
    target_accuracy: float = 0.5  # Acc_t for T_f / T_s
    oscillation_thresholds: Tuple[float, ...] = (0.02, 0.05, 0.10, 0.15)

    @property
    def mesh_devices(self) -> int:
        """Total mesh shard count: E*P under ``mesh_shape``, else the 1-D
        ``devices`` count.  What K (and a queue horizon) must divide."""
        if self.mesh_shape is not None:
            return self.mesh_shape[0] * self.mesh_shape[1]
        return self.devices

    def validate(self) -> None:
        assert self.mode in ("sync", "semi_async")
        assert 1 <= self.k <= self.n_clients
        assert self.aggregation in (
            "fedsgd", "fedavg", "sdga", "fedasync", "fedbuff", "fedopt")
        # an upload period must contain at least one local epoch; 0 would
        # make the client loop a no-op with no loss/update to report
        assert self.local_epochs >= 1, "local_epochs must be >= 1"
        assert self.local_batch_size >= 1
        # quantized channel: one scale per quant_block lanes.  Tiny blocks
        # would make the scale overhead rival the int8 payload, and the
        # fused Pallas kernels tile scales in whole power-of-two lane
        # tiles, so the granule must be a power of two
        assert (8 <= self.quant_block <= 2048
                and self.quant_block & (self.quant_block - 1) == 0), \
            "quant_block must be a power of two in [8, 2048]"
        # wire-format ladder (see the class docstring table)
        assert self.wire in ("f32", "q8", "q4", "topk"), self.wire
        if self.compress_updates:
            # legacy alias: only meaningful as "q8"; an explicit
            # different wire contradicts it
            assert self.wire in ("f32", "q8"), \
                (f"compress_updates=True is the legacy alias for "
                 f"wire='q8' — it conflicts with wire='{self.wire}'")
        assert 0.0 < self.topk_frac <= 1.0, \
            f"topk_frac={self.topk_frac} must be in (0, 1]"
        if self.wire == "topk":
            assert self.aggregation not in ("fedavg", "fedasync"), \
                ("wire='topk' is gradient-only: fedavg/fedasync upload "
                 "weights, and a sparse weight average would zero every "
                 "untransmitted coordinate")
        # every eval_every-th round is evaluated; 0 would record nothing
        assert self.eval_every >= 1, "eval_every must be >= 1"
        # scheduling subsystem knobs (repro.sched)
        assert self.sched_timing in ("static", "lognormal", "markov"), \
            self.sched_timing
        assert self.sched_policy in (
            "full", "uniform", "seafl", "fedqs", "ratelimit"), \
            self.sched_policy
        assert self.sched_rate_limit >= 0, "sched_rate_limit must be >= 0"
        # observability (repro.obs)
        assert self.trace_level in ("off", "round", "upload"), \
            self.trace_level
        if self.sched_policy == "ratelimit" and self.horizon in ("k",
                                                                 "queue"):
            # a count-triggered horizon must stay fillable: with fewer
            # admissions than the trigger needs, every later upload idles
            # and the round never closes (timeout/hybrid horizons close
            # on the clock instead, so any limit is safe there)
            target = (self.k if self.horizon == "k"
                      else (self.horizon_queue or self.k))
            limit = self.sched_rate_limit or self.k
            assert limit >= target, \
                (f"sched_rate_limit={limit} cannot fill a "
                 f"{self.horizon} horizon of {target} uploads")
        # aggregation horizon + server channel (tentpole PR 6)
        assert self.horizon in ("k", "queue", "timeout", "hybrid"), \
            self.horizon
        assert self.horizon_queue >= 0, "horizon_queue must be >= 0 (0 -> k)"
        if self.horizon in ("timeout", "hybrid"):
            assert self.horizon_timeout_s > 0.0, \
                f"horizon={self.horizon} needs horizon_timeout_s > 0"
            assert self.mode == "semi_async", \
                "timeout/hybrid horizons are semi-async constructs"
        assert self.server_channel in ("auto", "streaming", "buffered"), \
            self.server_channel
        if self.server_channel == "buffered":
            # the resident-rows oracle needs a fixed row count per horizon
            assert self.horizon in ("k", "queue"), \
                "buffered channel needs a fixed horizon (k or queue)"
        if self.server_channel == "streaming":
            assert self.mode == "semi_async", \
                "streaming accumulation is a semi-async construct (the " \
                "sync round produces its (K, D) rows as one program)"
        assert self.sched_jitter_sigma >= 0.0
        assert 0.0 <= self.sched_drop_p < 1.0, \
            "sched_drop_p must be in [0, 1) (1 would end every schedule)"
        assert self.sched_off_mean_s > 0.0
        assert self.sched_stale_cap >= 0
        # 0 means "all clients"; any C >= 1 keeps the buffer fillable
        # (an admitted client may upload several times per horizon)
        assert 0 <= self.sched_c <= self.n_clients, \
            f"sched_c={self.sched_c} must be in [0, n_clients]"
        assert isinstance(self.batch_clients, bool)
        assert self.wave_impl in ("vmap", "map", "auto"), self.wave_impl
        assert isinstance(self.wave_buckets, bool)
        # fault injection + defense (tentpole PR 8)
        for p in (self.fault_crash_p, self.fault_straggler_p,
                  self.fault_corrupt_p, self.fault_byzantine_p):
            assert 0.0 <= p <= 1.0, f"fault probability {p} not in [0, 1]"
        if (self.fault_crash_p or self.fault_straggler_p
                or self.fault_corrupt_p or self.fault_byzantine_p):
            assert self.mode == "semi_async", \
                ("fault injection rides the semi-async event heap; the "
                 "sync round has no per-upload schedule to perturb")
        assert self.fault_straggler_mult >= 1.0, \
            "fault_straggler_mult must be >= 1 (a spike, not a speedup)"
        assert self.fault_byzantine_rescale > 0.0
        assert self.fault_retry_backoff_s > 0.0
        assert self.fault_retry_cap >= 1, \
            "fault_retry_cap must be >= 1 (caps the backoff exponent)"
        assert self.defense in ("none", "screen", "clip"), self.defense
        if self.defense != "none":
            assert self.mode == "semi_async", \
                "defense screening guards the semi-async upload channel"
        if self.defense == "clip":
            assert self.defense_norm_cap > 0.0, \
                "defense='clip' needs defense_norm_cap > 0 (the norm cap)"
        assert self.defense_norm_cap >= 0.0
        # the podwise server reduction shard_maps the K buffer rows over
        # the mesh row axes, which requires an even split
        assert self.devices >= 1, "devices must be >= 1"
        if self.mesh_shape is not None:
            assert (isinstance(self.mesh_shape, tuple)
                    and len(self.mesh_shape) == 2), \
                f"mesh_shape={self.mesh_shape!r} must be an (edges, pods) " \
                "pair"
            e, p = self.mesh_shape
            assert e >= 1 and p >= 1, self.mesh_shape
            # the intra-edge reduce is log2(P) recursive-doubling rounds
            assert p & (p - 1) == 0, \
                (f"mesh_shape pods={p} must be a power of two (the "
                 "intra-edge tree reduce pairs shards by XOR rounds)")
            # devices stays the 1-D alias: setting BOTH to >1 device is
            # ambiguous unless they describe the same pool
            assert self.devices == 1 or self.devices == e * p, \
                (f"devices={self.devices} conflicts with mesh_shape="
                 f"{self.mesh_shape} ({e * p} devices); set one knob, or "
                 "make them agree")
        n_sh = self.mesh_devices
        if n_sh > 1:
            assert self.k % n_sh == 0, \
                (f"k={self.k} must be a multiple of the mesh device count "
                 f"{n_sh} (devices/mesh_shape: the channel rows shard "
                 "evenly over the row axes)")
            if self.horizon == "queue":
                q = self.horizon_queue or self.k
                assert q % n_sh == 0, \
                    (f"queue horizon of {q} uploads must be a multiple of "
                     f"the mesh device count {n_sh} (the channel rows "
                     "shard evenly over the row axes)")
