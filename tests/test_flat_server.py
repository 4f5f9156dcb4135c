"""Flat-buffer server round: codec roundtrip, Pallas-kernel-vs-oracle for
every buffered mode, the recompile guard, and batched-sync equivalence."""
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
from repro.models.vision_cnn import build_paper_model


# --------------------------- codec ---------------------------


def _tree(key):
    ks = jax.random.split(key, 3)
    return {"w": jax.random.normal(ks[0], (7, 5)),
            "b": jax.random.normal(ks[1], (11,)),
            "nest": {"c": jax.random.normal(ks[2], (3, 2, 2))}}


def test_codec_roundtrip(key):
    t = _tree(key)
    codec = flatbuf.PytreeCodec(t)
    assert codec.d == 7 * 5 + 11 + 3 * 2 * 2
    flat = codec.ravel(t)
    assert flat.shape == (codec.d,) and flat.dtype == jnp.float32
    back = codec.unravel(flat)
    for a, b in zip(jax.tree_util.tree_leaves(t),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_allclose(np.array(a), np.array(b), rtol=1e-6)
        assert a.dtype == b.dtype


def test_codec_ravel_delta_is_cumulative_gradient(key):
    start = _tree(key)
    end = jax.tree_util.tree_map(lambda x: x * 0.9 - 0.01, start)
    codec = flatbuf.PytreeCodec(start)
    lr = 0.05
    got = codec.ravel_delta(start, end, lr)
    want = codec.ravel(jax.tree_util.tree_map(
        lambda a, b: (a - b) / lr, start, end))
    np.testing.assert_allclose(np.array(got), np.array(want), atol=1e-5)


def test_write_slot_fills_rows(key):
    buf = flatbuf.alloc_buffer(3, 8)
    for i in range(3):
        vec = jnp.full((8,), float(i + 1))
        buf = flatbuf.write_slot(buf, vec, jnp.int32(i))
    np.testing.assert_allclose(np.array(buf),
                               np.tile(np.arange(1.0, 4.0)[:, None], (1, 8)))


# ------------------ kernel vs oracle, every mode ------------------


@pytest.mark.parametrize("mode", ["fedsgd", "fedavg", "fedbuff", "sdga"])
def test_flat_server_pallas_matches_oracle(mode, key):
    K, D = 6, 5000
    ks = jax.random.split(key, 3)
    buf = jax.random.normal(ks[0], (K, D), jnp.float32)
    params = jax.random.normal(ks[1], (D,), jnp.float32)
    if mode == "fedavg":
        wvec = jax.random.uniform(ks[2], (K,), jnp.float32) * 100 + 1
    elif mode == "fedsgd":
        wvec = jnp.ones((K,), jnp.float32)
    else:
        wvec = jnp.asarray([0, 1, 3, 0, 7, 2], jnp.float32)  # staleness

    outs = {}
    for backend in ("pallas_interpret", "xla"):
        srv = agg.FlatServer(mode, D, server_lr=0.3, alpha=0.5,
                             momentum=0.8, ema_anchor=0.05,
                             backend=backend, block_d=1024)
        opt = srv.init_opt(params)
        # copy inputs: the server program donates params/opt
        p, o, m = srv.step(jnp.array(params, copy=True), buf, wvec, opt)
        outs[backend] = (np.array(p), jax.tree_util.tree_map(np.array, o),
                         float(m["update_norm"]))
    p_k, o_k, n_k = outs["pallas_interpret"]
    p_x, o_x, n_x = outs["xla"]
    np.testing.assert_allclose(p_k, p_x, atol=1e-5, rtol=1e-5)
    assert n_k == pytest.approx(n_x, rel=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(o_k),
                    jax.tree_util.tree_leaves(o_x)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_fedasync_fold_matches_sequential_mix(key):
    """The flat fedasync server (mix-mode kernel + precomputed fold
    coefficients) must reproduce K sequential per-update mixes
    p <- (1-a_tau) p + a_tau w_i in arrival order, on both backends."""
    K, D = 5, 3000
    ks = jax.random.split(key, 2)
    u = jax.random.normal(ks[0], (K, D), jnp.float32)
    p = jax.random.normal(ks[1], (D,), jnp.float32)
    stal = [0, 2, 1, 5, 0]
    fa_alpha, alpha = 0.6, 0.5
    coef = agg.fedasync_coefficients(stal, fa_alpha, alpha)
    # the coefficients + the untouched-mass term partition unity
    keep = float(np.prod([1 - fa_alpha * (1 + s) ** -alpha for s in stal]))
    assert float(jnp.sum(coef)) == pytest.approx(1.0 - keep, rel=1e-5)

    seq = p
    for i in range(K):
        a = fa_alpha * float(agg.staleness_poly(jnp.float32(stal[i]),
                                                alpha))
        seq = (1.0 - a) * seq + a * u[i]

    for backend in ("pallas_interpret", "xla"):
        srv = agg.FlatServer("fedasync", D, server_lr=1.0,
                             backend=backend, block_d=1024)
        pn, _, m = srv.step(jnp.array(p, copy=True), u, coef,
                            srv.init_opt(p))
        np.testing.assert_allclose(np.array(pn), np.array(seq),
                                   atol=1e-5, rtol=1e-5)
        assert float(m["update_norm"]) > 0


def test_sdga_kernel_matches_flat_ref(key):
    from repro.kernels import ref, safl_agg
    K, D = 4, 3000
    ks = jax.random.split(key, 5)
    u = jax.random.normal(ks[0], (K, D))
    tau = jnp.asarray([0.0, 2.0, 5.0, 1.0])
    p = jax.random.normal(ks[1], (D,))
    mom = jax.random.normal(ks[2], (D,)) * 0.1
    ema = jax.random.normal(ks[3], (D,))
    kw = dict(server_lr=0.2, alpha=0.5, momentum=0.9, ema_anchor=0.03,
              ema_decay=0.97)
    got = safl_agg.sdga_aggregate(u, tau, p, mom, ema, block_d=1024,
                                  interpret=True, **kw)
    want = ref.sdga_flat_ref(u, tau, p, mom, ema, **kw)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.array(g), np.array(w), atol=1e-5,
                                   rtol=1e-5)


def test_fused_staleness_discount_matches_fedbuff(key):
    from repro.kernels import ref, safl_agg
    K, D = 5, 2500
    u = jax.random.normal(key, (K, D))
    tau = jnp.asarray([0.0, 4.0, 1.0, 9.0, 2.0])
    p = jnp.zeros((D,))
    got = safl_agg.safl_aggregate(u, tau, p, server_lr=0.5, mode="fedsgd",
                                  block_d=512, interpret=True,
                                  alpha=0.7, discount="poly")
    want = ref.fedbuff_flat_ref(u, tau, p, 0.5, alpha=0.7)
    np.testing.assert_allclose(np.array(got), np.array(want), atol=1e-5)


# --------------------------- engine integration ---------------------------


@pytest.fixture(scope="module")
def setup():
    ds = make_dataset("cifar10", n=400, seed=0, hw=16)
    tr, te = train_test_split(ds)
    shards = build_client_shards(tr, "iid", n_clients=6, batch_size=16)
    p0, s0, apply_fn = build_paper_model("cnn", jax.random.PRNGKey(0),
                                         width=4, image_size=16)
    return shards, te, p0, s0, apply_fn


@pytest.mark.parametrize("aggregation", ["fedsgd", "fedbuff", "sdga",
                                         "fedavg", "fedopt", "fedasync"])
def test_one_server_compilation_across_rounds(setup, aggregation):
    """The recompile guard: >= 3 rounds must reuse ONE compiled server
    program (shape-stable flat buffer, traced weight vector)."""
    shards, te, p0, s0, apply_fn = setup
    cfg = FLConfig(n_clients=6, k=3, mode="semi_async",
                   aggregation=aggregation, client_lr=0.05, server_lr=0.05,
                   target_accuracy=0.3)
    eng = FLEngine(cfg, apply_fn, "image", p0, s0, shards,
                   te.x[:100], te.y[:100])
    res = eng.run(4)
    assert res.metrics.summary()["rounds"] == 4
    assert eng._server.compile_count == 1


def test_batched_sync_round_matches_sequential(setup):
    """The vmapped SFL round must reproduce the sequential per-client
    path: same flat gradient buffer, same final states."""
    shards, te, p0, s0, apply_fn = setup
    codec = flatbuf.PytreeCodec(p0)
    round_fn = make_batched_local_train(apply_fn, "image", "grad", 1)
    epoch_fn = make_local_train(apply_fn, "image")
    active = [0, 2, 4]
    lr = 0.05
    xs = np.stack([shards[i]["xs"] for i in active])
    ys = np.stack([shards[i]["ys"] for i in active])
    mask = np.stack([shards[i]["mask"] for i in active])
    vecs, states, _ = round_fn(p0, s0, xs, ys, mask, lr)
    assert vecs.shape == (3, codec.d)
    for row, i in enumerate(active):
        w_end, _, _ = epoch_fn(p0, s0, shards[i]["xs"], shards[i]["ys"],
                               shards[i]["mask"], lr)
        want = codec.ravel_delta(p0, w_end, lr)
        np.testing.assert_allclose(np.array(vecs[row]), np.array(want),
                                   atol=2e-5)


def test_update_norm_recorded(setup):
    shards, te, p0, s0, apply_fn = setup
    cfg = FLConfig(n_clients=6, k=3, mode="sync", aggregation="fedsgd",
                   client_lr=0.05, server_lr=0.05, target_accuracy=0.3)
    eng = FLEngine(cfg, apply_fn, "image", p0, s0, shards,
                   te.x[:100], te.y[:100])
    res = eng.run(2)
    assert all(r.update_norm > 0 for r in res.metrics.records)


def test_local_epochs_zero_rejected():
    with pytest.raises(AssertionError):
        FLConfig(local_epochs=0).validate()
