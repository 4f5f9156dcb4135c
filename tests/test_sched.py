"""Scheduling subsystem (PR 5 tentpole): stochastic device-time models,
participation policies, staleness-aware adaptive reweighting.

Covers: sequential-vs-batched schedule parity under every timing model x
all 6 aggregation modes (the schedule trace — staleness histogram,
simulated times, byte accounting, participation — must be EXACTLY equal;
trained params equal up to vmap-lowering fp jitter), policy behavior
(uniform C=N == full bit-exact, SEAFL staleness cap, FedQS reweighting),
the compile-count guard (policies don't break wave bucketing's O(log K)
bound), speed-mutation-safe heap resume, the device-resident scheduling
stats, and the CI sched-smoke leg (tiny lognormal + adaptive config, 1
or 4 virtual devices)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core import FLEngine
from repro.core.client import make_batched_hetero_train
from repro.data import build_client_shards, make_dataset, train_test_split
from repro.models.lstm import build_lstm
from repro.sched import UPLOAD, WAKE, EventQueue, Scheduler
from repro.sched.timing import LognormalTiming, PRNGStream, StaticTiming

MODES = ("fedsgd", "fedavg", "fedasync", "fedbuff", "fedopt", "sdga")
NDEV = jax.device_count()


@pytest.fixture(scope="module")
def setup():
    ds = make_dataset("sentiment140", n=400, seed=0)
    tr, te = train_test_split(ds)
    shards = build_client_shards(tr, "iid", n_clients=8, batch_size=8)
    p0, s0, apply_fn = build_lstm(jax.random.PRNGKey(0), "sentiment",
                                  embed=2, hidden=4)
    return shards, te, p0, s0, apply_fn


def _run(setup, aggregation="fedsgd", batched=True, rounds=4,
         mode="semi_async", **kw):
    shards, te, p0, s0, apply_fn = setup
    slr = {"fedsgd": 0.05, "sdga": 0.05, "fedbuff": 0.05,
           "fedopt": 0.005}.get(aggregation, 1.0)
    cfg = FLConfig(n_clients=8, k=4, mode=mode,
                   aggregation=aggregation, client_lr=0.05, server_lr=slr,
                   target_accuracy=0.9, speed_sigma=0.8,
                   batch_clients=batched, **kw)
    eng = FLEngine(cfg, apply_fn, "sentiment", p0, s0, shards,
                   te.x[:32], te.y[:32])
    return eng.run(rounds), eng


def _assert_schedule_equal(ra, rb):
    """The schedule trace must be EXACTLY equal (both paths run the same
    host float arithmetic over the same draws — bit-exact on CPU)."""
    assert ra.staleness_hist == rb.staleness_hist
    assert ra.participation.tolist() == rb.participation.tolist()
    assert ra.metrics.total_tx_bytes() == rb.metrics.total_tx_bytes()
    assert ra.metrics.total_rx_bytes() == rb.metrics.total_rx_bytes()
    assert [r.sim_time for r in ra.metrics.records] == \
        [r.sim_time for r in rb.metrics.records]
    assert ra.sched_stats["rejected_uploads"] == \
        rb.sched_stats["rejected_uploads"]
    assert ra.sched_stats["no_shows"] == rb.sched_stats["no_shows"]


# --------------- batched vs sequential, per timing model ---------------


@pytest.mark.parametrize("timing", ["lognormal", "markov"])
@pytest.mark.parametrize("aggregation", MODES)
def test_batched_matches_sequential_per_timing(setup, aggregation, timing):
    """Stochastic timing draws are counter-keyed per (client, event), so
    the horizon-batched path must replay the sequential schedule exactly
    under every model (the static model is covered by
    test_engine_batched, which now routes through the scheduler too)."""
    kw = dict(sched_timing=timing, sched_jitter_sigma=0.5)
    if timing == "markov":
        kw.update(sched_drop_p=0.3, sched_off_mean_s=2.0)
    rb, eb = _run(setup, aggregation, True, **kw)
    rs, es = _run(setup, aggregation, False, **kw)
    _assert_schedule_equal(rb, rs)
    np.testing.assert_allclose(np.asarray(eb._flat_params),
                               np.asarray(es._flat_params),
                               atol=1e-4, rtol=1e-4)


def test_q8_channel_composes_with_policies(setup):
    """Quantized channel + selective policy + stochastic timing: the two
    engine paths still agree."""
    kw = dict(sched_timing="lognormal", sched_policy="uniform", sched_c=5,
              compress_updates=True)
    rb, eb = _run(setup, "fedsgd", True, **kw)
    rs, es = _run(setup, "fedsgd", False, **kw)
    _assert_schedule_equal(rb, rs)
    assert rb.sched_stats["rejected_uploads"] > 0
    np.testing.assert_allclose(np.asarray(eb._flat_params),
                               np.asarray(es._flat_params),
                               atol=1e-4, rtol=1e-4)


def test_stochastic_schedules_are_seeded_and_distinct(setup):
    """Same sched_seed -> identical schedule; different seed or sigma ->
    different event times; static is deterministic."""
    t = lambda res: [r.sim_time for r in res.metrics.records]
    a, _ = _run(setup, sched_timing="lognormal")
    b, _ = _run(setup, sched_timing="lognormal")
    c, _ = _run(setup, sched_timing="lognormal", sched_seed=1)
    d, _ = _run(setup)
    assert t(a) == t(b)
    assert t(a) != t(c)
    assert t(a) != t(d)


def test_markov_emits_no_shows(setup):
    res, _ = _run(setup, sched_timing="markov", sched_drop_p=0.5,
                  rounds=6)
    assert res.sched_stats["no_shows"] > 0
    # dropped clients rejoin: the schedule still fills every round
    assert len(res.metrics.records) == 6


# ----------------------------- policies -----------------------------


def test_uniform_c_equals_n_is_full_bit_exact(setup):
    """C = N admits everyone: identical schedule AND identical bits (the
    policy layer must be a true no-op then — the CI parity leg)."""
    rf, ef = _run(setup, "fedsgd", True)
    ru, eu = _run(setup, "fedsgd", True, sched_policy="uniform", sched_c=8)
    _assert_schedule_equal(rf, ru)
    np.testing.assert_array_equal(np.asarray(ef._flat_params),
                                  np.asarray(eu._flat_params))


def test_uniform_sampling_restricts_participation(setup):
    res, eng = _run(setup, "fedsgd", True, sched_policy="uniform",
                    sched_c=2, rounds=6)
    assert res.sched_stats["rejected_uploads"] > 0
    # every admitted upload came from that round's sampled set, so no
    # round's slot-cids exceed C distinct clients; globally, rejections
    # + admissions must cover every upload event
    assert int(res.participation.sum()) == 6 * 4
    assert len(res.metrics.records) == 6


def test_seafl_caps_buffered_staleness(setup):
    """The cap bounds what reaches the buffer; too-stale clients resync
    (staleness resets) instead of deadlocking."""
    cap = 1
    res, _ = _run(setup, "fedsgd", True, sched_policy="seafl",
                  sched_stale_cap=cap, rounds=6,
                  sched_timing="lognormal", sched_jitter_sigma=1.0)
    assert max(res.staleness_hist) <= cap
    assert len(res.metrics.records) == 6
    # a generous cap admits everything: identical to full
    rf, ef = _run(setup, "fedsgd", True)
    rc, ec = _run(setup, "fedsgd", True, sched_policy="seafl",
                  sched_stale_cap=10_000)
    _assert_schedule_equal(rf, rc)
    np.testing.assert_array_equal(np.asarray(ef._flat_params),
                                  np.asarray(ec._flat_params))


@pytest.mark.parametrize("aggregation", MODES)
def test_fedqs_reweighting_all_modes(setup, aggregation):
    """FedQS admits everyone (schedule == full's) but rescales the
    aggregation coefficients — external_discount server path — so the
    trained params must differ from full while the two engine paths
    still agree with each other."""
    rq, eq = _run(setup, aggregation, True, sched_policy="fedqs")
    rs, es = _run(setup, aggregation, False, sched_policy="fedqs")
    _assert_schedule_equal(rq, rs)
    np.testing.assert_allclose(np.asarray(eq._flat_params),
                               np.asarray(es._flat_params),
                               atol=1e-4, rtol=1e-4)
    assert eq._server.external_discount
    rf, ef = _run(setup, aggregation, True)
    _assert_schedule_equal(rq, rf)  # same events, different weights
    assert not np.array_equal(np.asarray(eq._flat_params),
                              np.asarray(ef._flat_params))
    assert all(np.isfinite(r.loss) for r in rq.metrics.records)


def test_fedqs_external_discount_matches_manual_weights(setup):
    """The externally-composed weight vector (host base-discount x score)
    must equal what the engine hands the server."""
    _, eng = _run(setup, "fedbuff", True, sched_policy="fedqs")
    stal, sizes = [3, 0, 1, 2], [10, 20, 30, 40]
    w = np.asarray(eng._weight_vector(stal, sizes))
    score = eng.sched.policy.score(stal, sizes)
    base = np.power(1.0 + np.asarray(stal, np.float32),
                    -np.float32(eng.cfg.staleness_alpha))
    np.testing.assert_allclose(w, base * score, rtol=1e-6)
    # score favors large-n, low-staleness clients
    s = eng.sched.policy.score([0, 5], [100, 100])
    assert s[0] > s[1]


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "q8"])
@pytest.mark.parametrize("mode", ["fedsgd", "fedavg", "fedbuff", "sdga",
                                  "fedopt", "fedasync"])
def test_external_discount_backend_parity(mode, quantized):
    """FlatServer(external_discount=True) must apply the precomputed
    weight vector identically on the jnp oracle and the Pallas kernels
    (interpret mode) — the adaptive policies' server path, including the
    sdga kernels' new discount switch."""
    from repro.core.aggregation import FlatServer
    from repro.core.flatbuf import PytreeCodec

    rng = np.random.default_rng(0)
    k, d, qb = 4, 1024, 256
    buf = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
    params = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    wvec = jnp.asarray([0.4, 1.3, 0.7, 1.0], jnp.float32)
    if quantized:
        codec = PytreeCodec({"w": np.zeros((d,), np.float32)}, qblock=qb)
        qs = [codec.ravel_q8_nores({"w": np.asarray(buf[i])})
              for i in range(k)]
        fbuf = (jnp.stack([q for q, _ in qs]),
                jnp.stack([s for _, s in qs]))
    else:
        fbuf = buf
    outs = []
    for backend in ("xla", "pallas_interpret"):
        srv = FlatServer(mode, d, server_lr=0.1, backend=backend,
                         quantized=quantized, qblock=qb,
                         external_discount=True, donate=False)
        p, _, m = srv.step(params, fbuf, wvec, srv.init_opt(params))
        outs.append((np.asarray(p), float(m["weight_sum"])))
    np.testing.assert_allclose(outs[0][0], outs[1][0],
                               atol=2e-5, rtol=2e-5)
    # weight_sum reads the external vector as-is (no in-program discount)
    for _, ws in outs:
        assert ws == pytest.approx(float(jnp.sum(wvec)), rel=1e-6)


# ----------------------- compile-count guard -----------------------


def test_policies_keep_wave_bucketing_olog_k(setup):
    """Selective policies churn wave shapes (rejected uploads shrink and
    reshuffle horizons); bucketing must still bound the wave-program
    count at O(log K), with ONE server compile."""
    shards, te, _, _, _ = setup
    p0, s0, apply_fn = build_lstm(jax.random.PRNGKey(3), "sentiment",
                                  embed=2, hidden=4)
    cfg = FLConfig(n_clients=8, k=8, mode="semi_async",
                   aggregation="fedsgd", client_lr=0.05, server_lr=0.05,
                   target_accuracy=0.9, speed_sigma=1.5,
                   sched_timing="lognormal", sched_jitter_sigma=1.0,
                   sched_policy="seafl", sched_stale_cap=2)
    eng = FLEngine(cfg, apply_fn, "sentiment", p0, s0, shards,
                   te.x[:32], te.y[:32])
    eng.run(20)
    wave_fn = make_batched_hetero_train(
        apply_fn, "sentiment", "grad", 1, eng.codec,
        impl=eng.wave_impl_resolved, mesh=None)
    n_buckets = int(math.log2(cfg.k)) + 1
    assert wave_fn._cache_size() <= n_buckets, \
        (wave_fn._cache_size(), set(eng.wave_size_hist))
    assert eng._server.compile_count == 1


# ------------------- events: speed-safe heap resume -------------------


class _C:
    def __init__(self, cid, speed, comm=1.0, n=100):
        self.cid, self.speed, self.comm_time = cid, speed, comm
        self.n_samples = n
        self.rng = np.random.default_rng(cid)


def test_event_queue_rescales_on_speed_mutation():
    """The _epoch_time fix: pending event times embed the scheduled
    compute duration; mutating ClientState.speed across run() calls must
    rescale that portion (compute ~ 1/speed), not replay stale times."""
    clients = [_C(0, 1.0), _C(1, 2.0)]
    timing = StaticTiming(lambda c: c.n_samples / (10.0 * c.speed))
    q = EventQueue()
    q.resume(clients, timing)
    before = {cid: (t, comp) for t, cid, _, comp in q._heap}
    assert before[0][1] == pytest.approx(10.0)  # 100 / (10 * 1.0)
    clients[0].speed = 4.0  # 4x faster -> pending compute shrinks 4x
    q.resume(clients, timing)
    after = {cid: (t, comp) for t, cid, _, comp in q._heap}
    assert after[0][1] == pytest.approx(before[0][1] / 4.0)
    assert after[0][0] == pytest.approx(
        before[0][0] - before[0][1] + before[0][1] / 4.0)
    # untouched client unchanged
    assert after[1] == before[1]
    # no mutation -> resume is a no-op
    q.resume(clients, timing)
    assert {cid: (t, comp) for t, cid, _, comp in q._heap} == after


def test_engine_speed_mutation_across_runs(setup):
    """An engine whose client speeds are mutated between run() calls
    keeps a consistent (monotone-time) schedule."""
    _, eng = _run(setup, "fedsgd", True, rounds=3)
    for c in eng.clients:
        c.speed *= 3.0
    res = eng.run(6)
    times = [r.sim_time for r in res.metrics.records]
    assert times == sorted(times)
    assert len(res.metrics.records) == 6


def test_prng_stream_is_counter_deterministic():
    a, b = PRNGStream(7), PRNGStream(7)
    # interleaving differs; per-(cid, counter) values must not
    da = [a.draw(0), a.draw(1), a.draw(0)]
    db_1 = [b.draw(1)]
    db_0 = [b.draw(0), b.draw(0)]
    np.testing.assert_array_equal(da[1], db_1[0])
    np.testing.assert_array_equal(da[0], db_0[0])
    np.testing.assert_array_equal(da[2], db_0[1])
    assert not np.array_equal(PRNGStream(8).draw(0), da[0])


# ------------------- device-resident sched stats -------------------


def test_device_sched_stats_match_host_accounting(setup):
    """The DeviceMetricsRing staleness histogram / participation counts
    (one host transfer at run end) must agree with the host-side dict
    and scheduler counts."""
    res, eng = _run(setup, "fedsgd", True, rounds=6,
                    sched_timing="lognormal", sched_jitter_sigma=1.0)
    bins = res.sched_stats["staleness_bins"]
    host = np.zeros_like(bins)
    for s, n in res.staleness_hist.items():
        host[min(s, len(bins) - 1)] += n
    np.testing.assert_array_equal(bins, host)
    np.testing.assert_array_equal(eng._dev_participation,
                                  res.participation)
    assert int(bins.sum()) == 6 * 4  # K uploads per round


def test_sfl_counts_participation(setup):
    res, _ = _run(setup, "fedavg", True, rounds=3, mode="sync")
    assert int(res.participation.sum()) == 3 * 4


# --------------------------- validation ---------------------------


def test_sched_config_validated():
    FLConfig(sched_timing="lognormal", sched_policy="fedqs").validate()
    with pytest.raises(AssertionError):
        FLConfig(sched_timing="gaussian").validate()
    with pytest.raises(AssertionError):
        FLConfig(sched_policy="random").validate()
    with pytest.raises(AssertionError):
        FLConfig(sched_drop_p=1.0).validate()
    with pytest.raises(AssertionError):
        FLConfig(sched_c=99).validate()
    with pytest.raises(AssertionError):
        FLConfig(sched_stale_cap=-1).validate()


# ------------------------- CI sched-smoke -------------------------


@pytest.mark.parametrize("devices", [1, 4])
def test_smoke_lognormal_adaptive_selection(setup, devices):
    """The CI sched-smoke leg: a tiny lognormal + adaptive-selection
    config through the batched engine (1 and 4 virtual devices — the 4
    case runs under XLA_FLAGS=--xla_force_host_platform_device_count=4),
    plus the uniform C=N == full parity assert."""
    if devices > NDEV:
        pytest.skip(f"needs {devices} jax devices, have {NDEV}")
    kw = dict(sched_timing="lognormal", devices=devices)
    # adaptive selection: seafl drops stale clients, fedqs reweights
    ra, ea = _run(setup, "fedsgd", True, sched_policy="seafl",
                  sched_stale_cap=2, sched_jitter_sigma=1.0, **kw)
    assert len(ra.metrics.records) == 4
    assert all(np.isfinite(r.loss) for r in ra.metrics.records)
    rq, _ = _run(setup, "sdga", True, sched_policy="fedqs", **kw)
    assert all(np.isfinite(r.loss) for r in rq.metrics.records)
    # uniform C = N must reproduce full participation bit-exactly
    rf, ef = _run(setup, "fedsgd", True, **kw)
    ru, eu = _run(setup, "fedsgd", True, sched_policy="uniform",
                  sched_c=8, **kw)
    _assert_schedule_equal(rf, ru)
    np.testing.assert_array_equal(np.asarray(ef._flat_params),
                                  np.asarray(eu._flat_params))
