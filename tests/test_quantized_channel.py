"""Lossy wire formats (q8 / q4 / topk): codec quantized emit programs,
fused dequant-aggregate server parity vs the f32 oracle for every buffered
mode, stochastic-rounding determinism, error-feedback telescoping, SFL
batched-vs-sequential parity with compression on, and engine integration
(byte accounting, bit-identical seq-vs-batched q4 runs, one-compile
guard)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core import FLEngine
from repro.core import aggregation as agg
from repro.core import flatbuf
from repro.core.client import make_batched_local_train, make_local_train
from repro.data import build_client_shards, make_dataset, train_test_split
from repro.kernels import ref
from repro.models.vision_cnn import build_paper_model


def _tree(key):
    ks = jax.random.split(key, 3)
    return {"w": jax.random.normal(ks[0], (40, 30)),
            "b": jax.random.normal(ks[1], (17,)),
            "nest": {"c": jax.random.normal(ks[2], (6, 5, 4))}}


def _dequant_row(q, s, qblock):
    return ref.dequant_flat_ref(q[None], s[None], qblock)[0]


# --------------------------- codec q8 programs ---------------------------


def test_ravel_delta_q8_roundtrip_and_residual(key):
    start = _tree(key)
    end = jax.tree_util.tree_map(lambda x: x * 0.9 - 0.01, start)
    codec = flatbuf.PytreeCodec(start, qblock=64)
    lr = 0.05
    q, s, res = codec.ravel_delta_q8(start, end, lr, codec.zero_residual())
    assert q.shape == (codec.dq,) and q.dtype == jnp.int8
    assert s.shape == (codec.n_qblocks,)
    delta = jnp.pad(codec.ravel_delta(start, end, lr),
                    (0, codec.dq - codec.d))
    deq = _dequant_row(q, s, codec.qblock)
    # the residual is the exact quantization error: deq + res == input
    np.testing.assert_allclose(np.array(deq + res), np.array(delta),
                               atol=1e-5, rtol=1e-5)
    # roundtrip error bounded by half a quantization step per block
    err = np.abs(np.array(deq - delta)).reshape(codec.n_qblocks, -1)
    bound = np.array(s)[:, None] * 0.5 + 1e-6
    assert (err <= bound).all()


def test_quantize_rows_matches_per_row(key):
    codec = flatbuf.PytreeCodec(_tree(key), qblock=64)
    K = 4
    vecs = jax.random.normal(key, (K, codec.d), jnp.float32)
    res = jax.random.normal(jax.random.PRNGKey(7), (K, codec.dq)) * 0.01
    qk, sk, rk = codec.quantize_rows(vecs, res)
    for k in range(K):
        tree_k = codec.unravel(vecs[k])
        qs, ss, rs = codec.ravel_q8(tree_k, res[k])
        np.testing.assert_array_equal(np.array(qk[k]), np.array(qs))
        np.testing.assert_allclose(np.array(sk[k]), np.array(ss), rtol=1e-6)
        np.testing.assert_allclose(np.array(rk[k]), np.array(rs), atol=1e-6)


def test_quant_buffer_write_fills_rows(key):
    codec = flatbuf.PytreeCodec(_tree(key), qblock=64)
    qbuf = flatbuf.QuantBuffer(3, codec.d, codec.qblock)
    rows = []
    for i in range(3):
        t = jax.tree_util.tree_map(
            lambda x, i=i: x * (i + 1),
            _tree(jax.random.PRNGKey(i)))
        q, s, _ = codec.ravel_q8(t, codec.zero_residual())
        qbuf.write(q, s, i)
        rows.append((np.array(q), np.array(s)))
    qs, ss = qbuf.views
    for i, (q, s) in enumerate(rows):
        np.testing.assert_array_equal(np.array(qs[i]), q)
        np.testing.assert_allclose(np.array(ss[i]), s, rtol=1e-6)


# ---------------- fused dequant-aggregate vs f32 oracle ----------------


@pytest.mark.parametrize("mode", ["fedsgd", "fedavg", "fedbuff", "fedopt",
                                  "sdga", "fedasync"])
def test_quantized_server_matches_f32_oracle(mode, key):
    """ravel-q8 -> fused dequant-aggregate reproduces the f32
    FlatServer.step within quantization tolerance (<= 2e-2 relative
    update-norm error), on both the interpret-Pallas and xla backends."""
    K, D, QB = 6, 5000, 512
    ks = jax.random.split(key, 3)
    buf = jax.random.normal(ks[0], (K, D), jnp.float32) * 0.1
    params = jax.random.normal(ks[1], (D,), jnp.float32)
    if mode == "fedavg":
        wvec = jax.random.uniform(ks[2], (K,), jnp.float32) * 100 + 1
    elif mode == "fedsgd":
        wvec = jnp.ones((K,), jnp.float32)
    elif mode == "fedasync":
        # folded per-update mix coefficients over a staleness vector
        wvec = agg.fedasync_coefficients([0, 1, 3, 0, 7, 2], 0.6, 0.5)
    else:
        wvec = jnp.asarray([0, 1, 3, 0, 7, 2], jnp.float32)  # staleness

    codec_dq = -(-D // QB) * QB
    q, s, _ = jax.vmap(
        lambda v: _quantize_vec(v, D, codec_dq, QB))(buf)

    outs = {}
    for backend in ("pallas_interpret", "xla"):
        srv = agg.FlatServer(mode, D, server_lr=0.3, alpha=0.5,
                             momentum=0.8, ema_anchor=0.05,
                             backend=backend, block_d=1024,
                             quantized=True, qblock=QB)
        opt = srv.init_opt(params)
        p, o, m = srv.step(jnp.array(params, copy=True), (q, s), wvec, opt)
        outs[backend] = (np.array(p), float(m["update_norm"]),
                         jax.tree_util.tree_map(np.array, o))
    # backends agree to fp tolerance (same math, different lowering)
    np.testing.assert_allclose(outs["pallas_interpret"][0], outs["xla"][0],
                               atol=1e-5, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(outs["pallas_interpret"][2]),
                    jax.tree_util.tree_leaves(outs["xla"][2])):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)

    if mode == "fedasync":
        # the folded-mix q8 oracle reproduces the fused server exactly
        want = ref.fedasync_flat_q8_ref(q, s, wvec, params, QB)
        for backend in outs:
            np.testing.assert_allclose(outs[backend][0], np.array(want),
                                       atol=1e-5, rtol=1e-5)

    # f32 oracle on the unquantized buffer
    srv32 = agg.FlatServer(mode, D, server_lr=0.3, alpha=0.5,
                           momentum=0.8, ema_anchor=0.05, backend="xla")
    o32 = srv32.init_opt(params)
    p32, _, m32 = srv32.step(jnp.array(params, copy=True), buf, wvec, o32)
    norm32 = float(m32["update_norm"])
    # fedopt's Adam step normalizes per-coordinate, so coordinates with
    # |g| below the quantization noise flip sign and each contributes a
    # full +-lr to the parameter distance (the update NORM still matches:
    # checked above at 2e-2) — bound it loosely; linear modes stay tight
    perr_bound = 0.15 if mode == "fedopt" else 2e-2
    for backend, (p_q8, norm_q8, _) in outs.items():
        rel = abs(norm_q8 - norm32) / max(norm32, 1e-12)
        assert rel <= 2e-2, (mode, backend, rel)
        perr = np.linalg.norm(p_q8 - np.array(p32))
        assert perr <= perr_bound * max(norm32, 1e-12), \
            (mode, backend, perr)


def _quantize_vec(v, d, dq, qblock):
    x = jnp.pad(v, (0, dq - d))
    blocks = x.reshape(-1, qblock)
    s = jnp.maximum(jnp.max(jnp.abs(blocks), axis=-1) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(blocks / s[:, None]), -127, 127).astype(jnp.int8)
    return q.reshape(dq), s, x


# ------------------- int8-dot large-K CPU reduction -------------------


def test_weighted_sum_q8_int8dot_matches_float_path(key):
    """Per-block-quantized coefficients + int32-accumulated integer dot
    reproduce the streaming float reduction within coefficient-rounding
    tolerance (<= 0.5/127 of the largest per-block coefficient)."""
    K, D, QB = 48, 4096, 64
    ks = jax.random.split(key, 3)
    buf = jax.random.normal(ks[0], (K, D), jnp.float32) * 0.1
    q, s = jax.vmap(lambda v: ref.quantize_ref(v.reshape(-1, QB)))(buf)
    q = q.reshape(K, D)
    w = jax.random.uniform(ks[1], (K,), jnp.float32)
    f = ref.weighted_sum_q8_ref(q, s, w, QB, int8_dot=False)
    i = ref.weighted_sum_q8_int8dot_ref(q, s, w, QB)
    rel = float(jnp.linalg.norm(f - i) / jnp.maximum(
        jnp.linalg.norm(f), 1e-12))
    assert rel <= 2e-2, rel
    # blockwise bound: error per lane <= half a coefficient-quantization
    # step times the summed |q| of that block's lanes is loose; check the
    # per-block scale bound instead
    c = w[:, None] * s
    cs = np.asarray(jnp.max(jnp.abs(c), axis=0) / 127.0)
    err = np.abs(np.asarray(f - i)).reshape(-1, QB).max(axis=1)
    bound = 0.5 * cs * 127.0 * K + 1e-6  # |q| <= 127 per addend
    assert (err <= bound).all()


def test_weighted_sum_q8_dispatches_int8dot_at_32_rows(key, monkeypatch):
    """With the platform gate pinned open (REPRO_INT8_DOT=1), K >= 32
    dispatches to the integer-dot path; below it stays on the fused
    streaming form."""
    monkeypatch.setenv("REPRO_INT8_DOT", "1")
    D, QB = 2048, 64
    for K, expect_int8 in ((31, False), (32, True), (64, True)):
        buf = jax.random.normal(key, (K, D), jnp.float32)
        q, s = jax.vmap(
            lambda v: ref.quantize_ref(v.reshape(-1, QB)))(buf)
        q = q.reshape(K, D)
        w = jnp.ones((K,), jnp.float32)
        auto = ref.weighted_sum_q8_ref(q, s, w, QB)
        forced = (ref.weighted_sum_q8_int8dot_ref(q, s, w, QB)
                  if expect_int8
                  else ref.weighted_sum_q8_ref(q, s, w, QB,
                                               int8_dot=False))
        np.testing.assert_array_equal(np.asarray(auto),
                                      np.asarray(forced))


def test_int8dot_auto_platform_gated(monkeypatch):
    """XLA CPU *emulates* the int8 GEMM (~8x slower than the chunked
    float form at K=64 — the `speedup_q8_vs_flat: 0.15` BENCH_agg
    regression), so auto dispatch requires a non-CPU backend.
    REPRO_INT8_DOT=1/0 overrides the platform gate but never the K
    threshold."""
    monkeypatch.delenv("REPRO_INT8_DOT", raising=False)
    if jax.default_backend() == "cpu":
        assert not ref.int8dot_auto(64)
        assert not ref.int8dot_auto(1024)
    monkeypatch.setenv("REPRO_INT8_DOT", "1")
    assert ref.int8dot_auto(ref.INT8_DOT_MIN_K)
    assert not ref.int8dot_auto(ref.INT8_DOT_MIN_K - 1)
    monkeypatch.setenv("REPRO_INT8_DOT", "0")
    assert not ref.int8dot_auto(64)


def test_cpu_q8_auto_matches_forced_float_path(key, monkeypatch):
    """On the auto gate the CPU q8 reduction must be BITWISE the chunked
    float form at every K — the regression guard for the K=64 cell."""
    monkeypatch.setenv("REPRO_INT8_DOT", "0")
    D, QB = 2048, 64
    for K in (8, 64):
        buf = jax.random.normal(key, (K, D), jnp.float32)
        q, s = jax.vmap(
            lambda v: ref.quantize_ref(v.reshape(-1, QB)))(buf)
        q = q.reshape(K, D)
        w = jnp.ones((K,), jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(ref.weighted_sum_q8_ref(q, s, w, QB)),
            np.asarray(ref.weighted_sum_q8_ref(q, s, w, QB,
                                               int8_dot=False)))


def test_quantized_server_large_k_uses_int8dot_and_stays_close(key):
    """FlatServer's q8 CPU path at K=64 (the int8-dot regime) still lands
    within quantization tolerance of the f32 oracle."""
    K, D, QB = 64, 4096, 512
    ks = jax.random.split(key, 2)
    buf = jax.random.normal(ks[0], (K, D), jnp.float32) * 0.1
    params = jax.random.normal(ks[1], (D,), jnp.float32)
    q, s, _ = jax.vmap(
        lambda v: _quantize_vec(v, D, -(-D // QB) * QB, QB))(buf)
    srv = agg.FlatServer("fedsgd", D, server_lr=0.3, backend="xla",
                         quantized=True, qblock=QB)
    p8, _, m8 = srv.step(jnp.array(params, copy=True), (q, s),
                         jnp.ones((K,)), srv.init_opt(params))
    srv32 = agg.FlatServer("fedsgd", D, server_lr=0.3, backend="xla")
    p32, _, m32 = srv32.step(jnp.array(params, copy=True), buf,
                             jnp.ones((K,)), srv32.init_opt(params))
    n32 = float(m32["update_norm"])
    assert abs(float(m8["update_norm"]) - n32) / n32 <= 2e-2
    perr = np.linalg.norm(np.asarray(p8) - np.asarray(p32))
    assert perr <= 2e-2 * n32


# ------------------- quantized BN-state payload -------------------


@pytest.fixture(scope="module")
def resnet_setup():
    """resnet18 is the paper model with real BN running stats — the
    non-trainable state payload the q8 channel now covers."""
    ds = make_dataset("cifar10", n=240, seed=0, hw=16)
    tr, te = train_test_split(ds)
    shards = build_client_shards(tr, "iid", n_clients=6, batch_size=8)
    p0, s0, apply_fn = build_paper_model("resnet18", jax.random.PRNGKey(0),
                                         width=4)
    return shards, te, p0, s0, apply_fn


def _run_resnet(resnet_setup, compress, batched, aggregation="fedavg",
                rounds=2):
    shards, te, p0, s0, apply_fn = resnet_setup
    cfg = FLConfig(n_clients=6, k=3, mode="semi_async",
                   aggregation=aggregation, client_lr=0.05, server_lr=1.0,
                   target_accuracy=0.9, compress_updates=compress,
                   batch_clients=batched)
    eng = FLEngine(cfg, apply_fn, "image", p0, s0, shards,
                   te.x[:32], te.y[:32])
    return eng.run(rounds), eng


def test_bn_state_payload_quantized(resnet_setup):
    """fedavg's BN-state upload rides ravel_q8: the accounted bytes must
    reflect int8 values + block scales for params AND state, and the
    engine must still aggregate a finite state."""
    rf, ef = _run_resnet(resnet_setup, False, True)
    rq, eq = _run_resnet(resnet_setup, True, True)
    assert eq._state_codec is not None
    state_q8 = eq._state_codec.dq + eq._state_codec.n_qblocks * 4
    params_q8 = eq.codec.dq + eq.codec.n_qblocks * 4
    want = int((params_q8 + state_q8) * 1.010)
    assert eq._upload_nbytes() == want
    # the full payload now compresses ~4x, state included
    assert rq.metrics.total_tx_bytes() < rf.metrics.total_tx_bytes() / 3
    for leaf in jax.tree_util.tree_leaves(eq.global_state):
        assert np.all(np.isfinite(np.asarray(leaf)))


def test_bn_state_quantization_parity_batched_vs_sequential(resnet_setup):
    """Both engine paths apply the same server-side state roundtrip, so
    batched-vs-sequential parity must survive the quantized state."""
    rb, eb = _run_resnet(resnet_setup, True, True)
    rs, es = _run_resnet(resnet_setup, True, False)
    assert rb.staleness_hist == rs.staleness_hist
    assert rb.metrics.total_tx_bytes() == rs.metrics.total_tx_bytes()
    for a, b in zip(rb.metrics.records, rs.metrics.records):
        assert a.accuracy == pytest.approx(b.accuracy, abs=2e-3)
    for lb, ls in zip(jax.tree_util.tree_leaves(eb.global_state),
                      jax.tree_util.tree_leaves(es.global_state)):
        np.testing.assert_allclose(np.asarray(lb), np.asarray(ls),
                                   atol=1e-4, rtol=1e-3)


def test_state_roundtrip_error_bounded(resnet_setup):
    """The server-side state view is within half a quantization step per
    block of the exact state."""
    shards, te, p0, s0, apply_fn = resnet_setup
    _, eng = _run_resnet(resnet_setup, True, True, rounds=1)
    codec = eng._state_codec
    flat = codec.ravel(s0)
    rt = codec.ravel(codec.roundtrip_q8(s0))
    q, scales = codec.ravel_q8_nores(s0)
    bound = np.repeat(np.asarray(scales), codec.qblock)[:codec.d] * 0.5
    assert (np.abs(np.asarray(rt - flat)) <= bound + 1e-6).all()


# --------------------------- error feedback ---------------------------


def test_error_feedback_drives_bias_below_no_ef(key):
    """A constant per-round update quantized T times: with error feedback
    the accumulated dequantized sum telescopes to within one quantization
    step of the true sum; without it the per-round bias accumulates."""
    tree = jax.tree_util.tree_map(lambda x: x * 0.01, _tree(key))
    codec = flatbuf.PytreeCodec(tree, qblock=64)
    true = np.array(jnp.pad(codec.ravel(tree), (0, codec.dq - codec.d)))
    T = 12
    acc_ef = np.zeros_like(true)
    acc_no = np.zeros_like(true)
    res = codec.zero_residual()
    for _ in range(T):
        q, s, res = codec.ravel_q8(tree, res)
        acc_ef += np.array(_dequant_row(q, s, codec.qblock))
        q0, s0, _ = codec.ravel_q8(tree, codec.zero_residual())
        acc_no += np.array(_dequant_row(q0, s0, codec.qblock))
    err_ef = np.linalg.norm(acc_ef - T * true)
    err_no = np.linalg.norm(acc_no - T * true)
    assert err_no > 0
    assert err_ef < err_no / 2, (err_ef, err_no)


# --------------------------- q4 packed wire ---------------------------


def test_q4_pack_unpack_roundtrip(key):
    q = jax.random.randint(key, (6, 64), -7, 8).astype(jnp.int8)
    p = ref.pack_q4_ref(q)
    assert p.shape == (6, 32) and p.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(ref.unpack_q4_ref(p)),
                                  np.asarray(q))


def test_ravel_delta_q4_residual_exact_and_bounded(key):
    """The q4 residual is the exact quantization error, and stochastic
    rounding stays within one int4 step per block."""
    start = _tree(key)
    end = jax.tree_util.tree_map(lambda x: x * 0.9 - 0.01, start)
    codec = flatbuf.PytreeCodec(start, qblock=64)
    lr = 0.05
    p, s, res = codec.ravel_delta_q4(start, end, lr,
                                     codec.zero_residual(), 0, 3, 0)
    assert p.shape == (codec.dq // 2,) and p.dtype == jnp.int8
    assert s.shape == (codec.n_qblocks,)
    delta = jnp.pad(codec.ravel_delta(start, end, lr),
                    (0, codec.dq - codec.d))
    deq = ref.dequant_q4_flat_ref(p[None], s[None], codec.qblock)[0]
    np.testing.assert_allclose(np.array(deq + res), np.array(delta),
                               atol=1e-5, rtol=1e-5)
    err = np.abs(np.array(deq - delta)).reshape(codec.n_qblocks, -1)
    bound = np.array(s)[:, None] * 1.0 + 1e-6  # SR: < one full step
    assert (err <= bound).all()


def test_q4_sr_counter_keyed_determinism(key):
    """Same (seed, cid, counter) -> bit-identical packed bytes and
    residuals; bumping the counter redraws the rounding."""
    start = _tree(key)
    end = jax.tree_util.tree_map(lambda x: x * 0.97, start)
    codec = flatbuf.PytreeCodec(start, qblock=64)
    a = codec.ravel_delta_q4(start, end, 0.05, codec.zero_residual(),
                             0, 2, 5)
    b = codec.ravel_delta_q4(start, end, 0.05, codec.zero_residual(),
                             0, 2, 5)
    c = codec.ravel_delta_q4(start, end, 0.05, codec.zero_residual(),
                             0, 2, 6)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(b[2]))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))


def test_quantize_rows_q4_matches_per_row(key):
    """The vmapped batch quantizer reproduces the sequential per-row
    programs bit-identically (fold_in vmaps elementwise) — the invariant
    that keeps seq and batched engine runs bit-identical under SR."""
    codec = flatbuf.PytreeCodec(_tree(key), qblock=64)
    K = 4
    vecs = jax.random.normal(key, (K, codec.d), jnp.float32)
    res = jax.random.normal(jax.random.PRNGKey(7), (K, codec.dq)) * 0.01
    cids = jnp.asarray([3, 0, 5, 1], jnp.int32)
    ctrs = jnp.asarray([0, 7, 2, 2], jnp.int32)
    pk, sk, rk = codec.quantize_rows_q4(vecs, res, 0, cids, ctrs)
    for k in range(K):
        tree_k = codec.unravel(vecs[k])
        ps, ss, rs = codec.ravel_q4(tree_k, res[k], 0,
                                    int(cids[k]), int(ctrs[k]))
        np.testing.assert_array_equal(np.array(pk[k]), np.array(ps))
        np.testing.assert_array_equal(np.array(sk[k]).view(np.int32),
                                      np.array(ss).view(np.int32))
        np.testing.assert_array_equal(np.array(rk[k]).view(np.int32),
                                      np.array(rs).view(np.int32))


@pytest.mark.parametrize("mode", ["fedsgd", "fedavg", "fedbuff", "fedopt",
                                  "sdga", "fedasync"])
def test_q4_server_matches_dense_dequant_oracle(mode, key):
    """FlatServer on the packed q4 wire == the f32 FlatServer on the
    dequantized dense rows, to fp tolerance, on both backends — the
    unpack-dequant really is fused losslessly into the aggregation."""
    K, D, QB = 6, 5000, 512
    ks = jax.random.split(key, 3)
    buf = jax.random.normal(ks[0], (K, D), jnp.float32) * 0.1
    params = jax.random.normal(ks[1], (D,), jnp.float32)
    if mode == "fedavg":
        wvec = jax.random.uniform(ks[2], (K,), jnp.float32) * 100 + 1
    elif mode == "fedsgd":
        wvec = jnp.ones((K,), jnp.float32)
    elif mode == "fedasync":
        wvec = agg.fedasync_coefficients([0, 1, 3, 0, 7, 2], 0.6, 0.5)
    else:
        wvec = jnp.asarray([0, 1, 3, 0, 7, 2], jnp.float32)
    dq = -(-D // QB) * QB
    x = jnp.pad(buf, ((0, 0), (0, dq - D)))
    u = jax.random.uniform(key, (K, dq // QB, QB))
    q, s = jax.vmap(ref.quantize_q4_ref)(x.reshape(K, -1, QB), u)
    p = ref.pack_q4_ref(q.reshape(K, dq))
    dense = ref.dequant_q4_flat_ref(p, s, QB)[:, :D]

    srv32 = agg.FlatServer(mode, D, server_lr=0.3, alpha=0.5,
                           momentum=0.8, ema_anchor=0.05, backend="xla")
    o32 = srv32.init_opt(params)
    p32, _, m32 = srv32.step(jnp.array(params, copy=True), dense, wvec, o32)
    for backend in ("pallas_interpret", "xla"):
        srv = agg.FlatServer(mode, D, server_lr=0.3, alpha=0.5,
                             momentum=0.8, ema_anchor=0.05,
                             backend=backend, block_d=1024,
                             wire="q4", qblock=QB)
        opt = srv.init_opt(params)
        pq, oq, mq = srv.step(jnp.array(params, copy=True), (p, s),
                              wvec, opt)
        np.testing.assert_allclose(np.array(pq), np.array(p32),
                                   atol=2e-5, rtol=2e-5)
        assert abs(float(mq["update_norm"]) - float(m32["update_norm"])) \
            <= 2e-4 * max(float(m32["update_norm"]), 1e-12)


# --------------------------- top-k sparse wire ---------------------------


def test_topk_codec_keeps_largest_and_feeds_residual(key):
    """ravel_delta_topk keeps the nk largest-|.| coordinates (up to the
    value-quantization step) and returns exactly the dropped + quant
    error as the residual."""
    start = _tree(key)
    end = jax.tree_util.tree_map(lambda x: x * 0.9 - 0.01, start)
    codec = flatbuf.PytreeCodec(start, qblock=64, topk_frac=0.1)
    lr = 0.05
    idx, qv, s, res = codec.ravel_delta_topk(start, end, lr,
                                             codec.zero_residual())
    assert idx.shape == (codec.nk,) and idx.dtype == jnp.int32
    assert qv.shape == (codec.nk,) and qv.dtype == jnp.int8
    assert s.shape == (codec.nk_qblocks,)
    delta = np.array(jnp.pad(codec.ravel_delta(start, end, lr),
                             (0, codec.dq - codec.d)))
    deq = np.array(ref.dequant_topk_ref(qv, s, codec.qblock))
    dense = np.zeros_like(delta)
    dense[np.array(idx)] = deq
    # residual telescopes: scatter(deq) + res == delta exactly
    np.testing.assert_allclose(dense + np.array(res), delta,
                               atol=1e-5, rtol=1e-5)
    # kept set is the true top-nk by |delta| (ties aside): the smallest
    # kept |value| must be >= the largest dropped |value| - quant step
    kept = np.zeros(delta.shape[0], bool)
    kept[np.array(idx)] = True
    step = float(np.max(np.array(s)))
    assert np.abs(delta[kept]).min() >= np.abs(delta[~kept]).max() - step


@pytest.mark.parametrize("mode", ["fedsgd", "fedbuff", "fedopt", "sdga"])
def test_topk_server_matches_dense_scatter_oracle(mode, key):
    """FlatServer on the sparse (idx, qv, scales) wire == the f32
    FlatServer on the densified rows.  Every backend takes the same XLA
    scatter for topk; both backend names must reach it."""
    K, D, QB, NK = 6, 5000, 64, 512
    ks = jax.random.split(key, 3)
    buf = jax.random.normal(ks[0], (K, D), jnp.float32) * 0.1
    params = jax.random.normal(ks[1], (D,), jnp.float32)
    wvec = (jnp.ones((K,), jnp.float32) if mode == "fedsgd"
            else jnp.asarray([0, 1, 3, 0, 7, 2], jnp.float32))
    _, idx = jax.lax.top_k(jnp.abs(buf), NK)
    vals = jnp.take_along_axis(buf, idx, axis=1)
    q, s = jax.vmap(ref.quantize_ref)(vals.reshape(K, -1, QB))
    q = q.reshape(K, NK)
    dense = np.zeros((K, D), np.float32)
    deq = np.array(ref.dequant_topk_ref(q, s, QB))
    for k in range(K):
        dense[k, np.array(idx[k])] = deq[k]

    srv32 = agg.FlatServer(mode, D, server_lr=0.3, alpha=0.5,
                           momentum=0.8, ema_anchor=0.05, backend="xla")
    p32, _, m32 = srv32.step(jnp.array(params, copy=True),
                             jnp.asarray(dense), wvec,
                             srv32.init_opt(params))
    for backend in ("pallas_interpret", "xla"):
        srv = agg.FlatServer(mode, D, server_lr=0.3, alpha=0.5,
                             momentum=0.8, ema_anchor=0.05,
                             backend=backend, block_d=1024,
                             wire="topk", qblock=QB)
        pt, _, mt = srv.step(jnp.array(params, copy=True),
                             (idx.astype(jnp.int32), q, s), wvec,
                             srv.init_opt(params))
        np.testing.assert_allclose(np.array(pt), np.array(p32),
                                   atol=2e-5, rtol=2e-5)


def test_topk_rejects_model_targets():
    """The sparse wire carries gradient deltas only — scattering a
    sparse row into a *weight* average would zero the missing
    coordinates.  Both the config and the server refuse."""
    for aggregation in ("fedavg", "fedasync"):
        with pytest.raises(AssertionError):
            FLConfig(aggregation=aggregation, wire="topk").validate()
        with pytest.raises(AssertionError):
            agg.FlatServer(aggregation, 1024, server_lr=1.0, wire="topk")


def test_wire_config_validated():
    with pytest.raises(AssertionError):
        FLConfig(wire="int2").validate()
    with pytest.raises(AssertionError):
        FLConfig(wire="topk", topk_frac=0.0).validate()
    with pytest.raises(AssertionError):
        FLConfig(wire="q4", compress_updates=True).validate()
    FLConfig(wire="q4").validate()
    FLConfig(wire="topk", aggregation="fedbuff").validate()


# ---------------- EF telescoping property (q4 + topk) ----------------

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:  # not in the image: seeded fallback below
    _HAVE_HYPOTHESIS = False


def _check_ef_telescopes(seed: int, wire: str):
    """Property: for ANY constant per-round delta, T lossy uploads with
    error feedback satisfy the exact telescoping identity
    sum_t dequant_t + residual_T == T * delta (up to fp), so the
    time-averaged wire error is bounded by ||res_T|| / T -> 0."""
    k0 = jax.random.PRNGKey(seed)
    tree = jax.tree_util.tree_map(lambda x: x * 0.02, _tree(k0))
    codec = flatbuf.PytreeCodec(tree, qblock=64, topk_frac=0.1)
    true = np.array(jnp.pad(codec.ravel(tree), (0, codec.dq - codec.d)))
    T = 8
    acc = np.zeros_like(true)
    res = codec.zero_residual()
    for t in range(T):
        if wire == "q4":
            p, s, res = codec.ravel_q4(tree, res, seed, 0, t)
            acc += np.array(ref.dequant_q4_flat_ref(p[None], s[None],
                                                    codec.qblock)[0])
        else:
            idx, qv, s, res = codec.ravel_topk(tree, res)
            deq = np.array(ref.dequant_topk_ref(qv, s, codec.qblock))
            dense = np.zeros_like(true)
            dense[np.array(idx)] = deq
            acc += dense
    scale = np.linalg.norm(T * true) + 1e-12
    # exact telescoping (fp accumulation tolerance only)
    assert np.linalg.norm(acc + np.array(res) - T * true) <= 1e-4 * scale
    # and the residual is bounded independently of T (no drift): q4
    # transmits every coordinate, so one SR step's worth; topk is a
    # delta-contractive compressor (keep fraction delta = nk/dq) whose
    # EF residual saturates at sqrt(1-d)/(1-sqrt(1-d)) * ||x||
    if wire == "q4":
        bound = np.linalg.norm(true) + 1e-6
    else:
        r = np.sqrt(1.0 - codec.nk / codec.dq)
        bound = (r / (1.0 - r) + 1.0) * np.linalg.norm(true) * 1.5 + 1e-6
    assert np.linalg.norm(np.array(res)) <= bound


if _HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**16), wire=st.sampled_from(["q4", "topk"]))
    def test_ef_telescoping_property(seed, wire):
        _check_ef_telescopes(seed, wire)
else:
    @pytest.mark.parametrize("wire", ["q4", "topk"])
    @pytest.mark.parametrize("seed", [0, 1, 7, 42, 1337])
    def test_ef_telescoping_property(seed, wire):
        _check_ef_telescopes(seed, wire)


# ------------------- engine integration / SFL parity -------------------


@pytest.fixture(scope="module")
def setup():
    ds = make_dataset("cifar10", n=400, seed=0, hw=16)
    tr, te = train_test_split(ds)
    shards = build_client_shards(tr, "iid", n_clients=6, batch_size=16)
    p0, s0, apply_fn = build_paper_model("cnn", jax.random.PRNGKey(0),
                                         width=4, image_size=16)
    return shards, te, p0, s0, apply_fn


def test_sfl_batched_matches_sequential_quantized(setup):
    """The vmapped SFL round with compression on must reproduce the
    sequential per-client quantized uploads: same int8 rows up to the
    quantization step of the (fp-jitter-close) f32 inputs."""
    shards, te, p0, s0, apply_fn = setup
    codec = flatbuf.PytreeCodec(p0)
    round_fn = make_batched_local_train(apply_fn, "image", "grad", 1)
    epoch_fn = make_local_train(apply_fn, "image")
    active = [0, 2, 4]
    lr = 0.05
    xs = np.stack([shards[i]["xs"] for i in active])
    ys = np.stack([shards[i]["ys"] for i in active])
    mask = np.stack([shards[i]["mask"] for i in active])
    vecs, _, _ = round_fn(p0, s0, xs, ys, mask, lr)
    qb, sb, _ = codec.quantize_rows(
        vecs, jnp.zeros((len(active), codec.dq), jnp.float32))
    for row, i in enumerate(active):
        w_end, _, _ = epoch_fn(p0, s0, shards[i]["xs"], shards[i]["ys"],
                               shards[i]["mask"], lr)
        q1, s1, _ = codec.ravel_delta_q8(p0, w_end, lr,
                                         codec.zero_residual())
        deq_b = np.array(_dequant_row(qb[row], sb[row], codec.qblock))
        deq_s = np.array(_dequant_row(q1, s1, codec.qblock))
        # inputs differ by fp jitter (~2e-5); dequantized rows may differ
        # by at most one quantization step on top of that
        tol = float(jnp.maximum(jnp.max(sb[row]), jnp.max(s1))) + 1e-4
        np.testing.assert_allclose(deq_b, deq_s, atol=tol)


@pytest.mark.parametrize("mode", ["sync", "semi_async"])
def test_quantized_engine_runs_learns_one_compile(setup, mode):
    shards, te, p0, s0, apply_fn = setup
    cfg = FLConfig(n_clients=6, k=3, mode=mode, aggregation="fedsgd",
                   client_lr=0.05, server_lr=0.05, target_accuracy=0.3,
                   compress_updates=True)
    eng = FLEngine(cfg, apply_fn, "image", p0, s0, shards,
                   te.x[:100], te.y[:100])
    res = eng.run(4)
    s = res.metrics.summary()
    assert s["rounds"] == 4
    assert s["best_accuracy"] > 0.15
    assert eng._server.compile_count == 1


def test_model_target_uploads_compress_too(setup):
    """fedavg / fedasync with compress_updates must transmit the quantized
    payload (int8 + block scales), not silently fall back to f32."""
    shards, te, p0, s0, apply_fn = setup

    def run(aggregation, compress):
        cfg = FLConfig(n_clients=6, k=3, mode="semi_async",
                       aggregation=aggregation, client_lr=0.05,
                       server_lr=1.0, target_accuracy=0.3,
                       compress_updates=compress)
        eng = FLEngine(cfg, apply_fn, "image", p0, s0, shards,
                       te.x[:100], te.y[:100])
        return eng.run(3)

    for aggregation in ("fedavg", "fedasync"):
        base = run(aggregation, False).metrics.total_tx_bytes()
        comp = run(aggregation, True).metrics.total_tx_bytes()
        # params AND BN state compress ~3.9x (the state rides ravel_q8
        # too — the cnn fixture has no state, resnet_setup covers it)
        assert comp < base / 2.5, (aggregation, base, comp)


def test_quant_block_validated():
    with pytest.raises(AssertionError):
        FLConfig(quant_block=4).validate()


# ------------------- engine wire matrix (q4 / topk) -------------------


def _run_wire(setup, wire, batched, aggregation="fedbuff", rounds=3,
              channel="auto"):
    shards, te, p0, s0, apply_fn = setup
    slr = {"fedsgd": 0.05, "sdga": 0.05, "fedbuff": 0.05,
           "fedopt": 0.005}.get(aggregation, 1.0)
    cfg = FLConfig(n_clients=6, k=3, mode="semi_async",
                   aggregation=aggregation, client_lr=0.05, server_lr=slr,
                   target_accuracy=0.9, wire=wire, batch_clients=batched,
                   server_channel=channel)
    eng = FLEngine(cfg, apply_fn, "image", p0, s0, shards,
                   te.x[:100], te.y[:100])
    return eng.run(rounds), eng


def _flat(eng):
    return np.asarray(eng._flat_params)


@pytest.mark.parametrize("aggregation", ["fedsgd", "fedavg"])
def test_wire_q4_batched_matches_sequential_bitwise(setup, aggregation):
    """The ISSUE acceptance bit: with the counter-keyed SR draws, the
    batched and sequential engines produce BIT-IDENTICAL q4 runs (same
    per-client counters regardless of global upload interleaving)."""
    rs, es = _run_wire(setup, "q4", False, aggregation)
    rb, eb = _run_wire(setup, "q4", True, aggregation)
    np.testing.assert_array_equal(_flat(es).view(np.int32),
                                  _flat(eb).view(np.int32))
    assert rs.staleness_hist == rb.staleness_hist
    assert rs.metrics.total_tx_bytes() == rb.metrics.total_tx_bytes()


def test_wire_topk_batched_matches_sequential_bitwise(setup):
    rs, es = _run_wire(setup, "topk", False)
    rb, eb = _run_wire(setup, "topk", True)
    np.testing.assert_array_equal(_flat(es).view(np.int32),
                                  _flat(eb).view(np.int32))
    assert rs.metrics.total_tx_bytes() == rb.metrics.total_tx_bytes()


def test_wire_byte_accounting_ratios(setup):
    """Transmitted bytes follow payload_nbytes: q4 ~8x and topk
    (frac=0.1 rounded up to whole blocks) >= 6x below the f32 wire, and
    the lossy runs still move the model."""
    rf, ef = _run_wire(setup, "f32", True)
    r4, e4 = _run_wire(setup, "q4", True)
    rt, et = _run_wire(setup, "topk", True)
    bf = rf.metrics.total_tx_bytes()
    b4 = r4.metrics.total_tx_bytes()
    bt = rt.metrics.total_tx_bytes()
    assert bf / b4 > 7.0, (bf, b4)
    assert bf / bt > 6.0, (bf, bt)
    for r in (r4, rt):
        assert np.isfinite(r.metrics.records[-1].accuracy)
        assert r.metrics.best_accuracy() > 0.1


def test_wire_q4_engine_one_compile(setup):
    _, eng = _run_wire(setup, "q4", True, "fedsgd", rounds=4)
    assert eng._server.compile_count == 1
