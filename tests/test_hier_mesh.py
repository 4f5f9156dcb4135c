"""Hierarchical (edge, pod) 2-D mesh aggregation (PR 9 tentpole).

Layer map: config validation + the cross-edge traffic model + the
host-side XOR tree-reduce oracle run on any device count (tier-1);
everything touching a real 2-D mesh needs >= 4 jax devices and skips
otherwise (the hierarchy CI job sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8``); one subprocess
test exercises the 8-virtual-device path from a single-device session.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core import FLEngine
from repro.core import aggregation as agg
from repro.data import build_client_shards, make_dataset, train_test_split
from repro.kernels import ref
from repro.models.lstm import build_lstm
from repro.obs.profile import CompileLog
from repro.sharding import flat as shflat
from repro.sharding import rules

NDEV = jax.device_count()
hier4 = pytest.mark.skipif(
    NDEV < 4, reason="needs >= 4 jax devices (set XLA_FLAGS="
    "--xla_force_host_platform_device_count before importing jax)")
multidevice = pytest.mark.skipif(NDEV < 2, reason="needs >1 jax device")

MODES = ("fedsgd", "fedavg", "fedasync", "fedbuff", "fedopt", "sdga")


# --------------------- config / topology validation ---------------------


def test_mesh_shape_validation():
    FLConfig(mesh_shape=(2, 2), k=4).validate()
    FLConfig(mesh_shape=(1, 4), k=4).validate()
    with pytest.raises(AssertionError):  # pods must be a power of two
        FLConfig(mesh_shape=(2, 3), k=6).validate()
    with pytest.raises(AssertionError):  # rows must split over E*P
        FLConfig(mesh_shape=(2, 2), k=6).validate()
    with pytest.raises(AssertionError):  # devices conflicts with mesh
        FLConfig(mesh_shape=(2, 2), devices=2, k=4).validate()
    # devices matching E*P is the explicit-redundant spelling: allowed
    FLConfig(mesh_shape=(2, 2), devices=4, k=4).validate()


def test_mesh_devices_property():
    assert FLConfig(mesh_shape=(2, 4), k=8).mesh_devices == 8
    assert FLConfig(devices=4, k=4).mesh_devices == 4
    assert FLConfig().mesh_devices == 1


def test_mesh_queue_horizon_must_split():
    with pytest.raises(AssertionError):
        FLConfig(mesh_shape=(2, 2), k=4, horizon="queue",
                 horizon_queue=6).validate()


def test_hier_mesh_rejects_oversized_pool():
    with pytest.raises(AssertionError):
        shflat.make_hier_mesh(NDEV + 1, 2)
    with pytest.raises(AssertionError):  # pow2 pods enforced at build too
        shflat.make_hier_mesh(1, 3)


def test_mesh_shape_helpers_without_mesh():
    assert shflat.mesh_shape(None) == (1, 1)
    assert not shflat.is_hier(None)
    assert shflat.reduce_axes(None) == shflat.POD_AXIS


# ----------------------- cross-edge traffic model -----------------------


def test_edge_traffic_model_reduction_is_pod_count():
    """Only E of the E*P shard partials cross the edge boundary, so the
    cross-edge bytes shrink by exactly P vs the flat global psum."""
    for (E, P) in [(2, 2), (2, 4), (4, 2), (8, 8)]:
        t = shflat.edge_traffic((E, P), 1000)
        assert t["mesh_shape"] == (E, P)
        assert t["cross_edge_partials"] == E
        assert t["cross_edge_bytes"] == E * 1004
        assert t["flat_cross_bytes"] == E * P * 1004
        assert t["cross_edge_reduction"] == float(P)


def test_edge_traffic_flat_mesh_is_the_baseline():
    """A 1-D (or absent) mesh has no edge boundary to save across: all N
    partials cross and the reduction factor is 1."""
    t = shflat.edge_traffic((1, 4), 1000)
    assert t["cross_edge_bytes"] == t["flat_cross_bytes"] == 4 * 1004
    assert t["cross_edge_reduction"] == 1.0
    t0 = shflat.edge_traffic(None, 1000)
    assert t0["cross_edge_reduction"] == 1.0


def test_cross_edge_roofline_helper():
    from repro.launch.mesh import ICI_BW, cross_edge_time_s
    assert cross_edge_time_s(ICI_BW) == pytest.approx(1.0)
    assert cross_edge_time_s(1000, link_bw=500.0) == pytest.approx(2.0)


# ------------------- XOR tree-reduce oracle (host) -------------------


def test_xor_tree_sum_ref_matches_np_sum(key):
    parts = [jax.random.normal(k, (64,), jnp.float32)
             for k in jax.random.split(key, 8)]
    got = np.asarray(ref.xor_tree_sum_ref(parts))
    np.testing.assert_allclose(got, np.sum(np.stack(parts), axis=0),
                               atol=1e-5, rtol=1e-5)


def test_xor_tree_sum_ref_rejects_non_pow2(key):
    with pytest.raises(AssertionError):
        ref.xor_tree_sum_ref([jnp.zeros(4)] * 3)


@hier4
def test_tree_reduce_bitwise_matches_xor_oracle(key):
    """The intra-edge ppermute tree reduce performs EXACTLY the XOR
    pairing additions of :func:`repro.kernels.ref.xor_tree_sum_ref` —
    bitwise, not just within tolerance — on every edge, and the
    cross-edge psum adds the edge partials."""
    from repro.kernels.safl_agg import edge_partial_reduce
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    E, Pods, D = 2, 2, 257
    mesh = shflat.make_hier_mesh(E, Pods)
    x = jax.random.normal(key, (E * Pods, D), jnp.float32) * 0.1

    def local(xs):
        return edge_partial_reduce(xs.reshape(-1), pod_size=Pods)

    got = np.asarray(jax.jit(shard_map(
        local, mesh=mesh, in_specs=(P(("edge", "pod"), None),),
        out_specs=P(), check_vma=False))(x))
    rows = [x[i] for i in range(E * Pods)]
    edge_partials = [ref.xor_tree_sum_ref(rows[e * Pods:(e + 1) * Pods])
                     for e in range(E)]
    want = np.asarray(edge_partials[0] + edge_partials[1])
    np.testing.assert_array_equal(got, want)


# ------------------------ server-level parity ------------------------


def _quantize(buf, D, QB):
    dq = -(-D // QB) * QB
    x = jnp.pad(buf, ((0, 0), (0, dq - D)))
    blocks = x.reshape(buf.shape[0], dq // QB, QB)
    s = jnp.maximum(jnp.max(jnp.abs(blocks), axis=-1) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(blocks / s[..., None]), -127,
                 127).astype(jnp.int8)
    return q.reshape(buf.shape[0], dq), s


def _q4_payload(buf, D, QB, key):
    dq = -(-D // QB) * QB
    x = jnp.pad(buf, ((0, 0), (0, dq - D)))
    u = jax.random.uniform(key, (buf.shape[0], dq // QB, QB))
    q, s = jax.vmap(ref.quantize_q4_ref)(x.reshape(buf.shape[0], -1, QB), u)
    return ref.pack_q4_ref(q.reshape(buf.shape[0], dq)), s


def _topk_payload(buf, nk, qb):
    _, idx = jax.lax.top_k(jnp.abs(buf), nk)
    vals = jnp.take_along_axis(buf, idx, axis=1)
    q, s = jax.vmap(ref.quantize_ref)(vals.reshape(buf.shape[0], -1, qb))
    return idx.astype(jnp.int32), q.reshape(buf.shape[0], nk), s


def _wvec(mode, K, key):
    if mode == "fedavg":
        return jax.random.uniform(key, (K,), jnp.float32) * 100 + 1
    if mode == "fedsgd":
        return jnp.ones((K,), jnp.float32)
    if mode == "fedasync":
        return agg.fedasync_coefficients([i % 7 for i in range(K)],
                                         0.6, 0.5)
    return jnp.asarray(np.arange(K) % 5, jnp.float32)


@hier4
@pytest.mark.parametrize("wire", ["f32", "q8", "q4", "topk"])
@pytest.mark.parametrize("mode", MODES)
def test_hier_server_matches_single_device(mode, wire, key):
    """FlatServer on the (2, 2) mesh — intra-edge tree reduce + one
    cross-edge psum — must reproduce the single-device fused round for
    every mode x wire at the 1-D mesh tolerances (the q8/q4 partial
    bodies dequantize per shard BEFORE the tree reduce, so edge partials
    are always f32 and nothing new accumulates in low precision)."""
    if wire == "topk" and mode in ("fedavg", "fedasync"):
        pytest.skip("sparse wire carries gradient deltas only")
    mesh = shflat.make_hier_mesh(2, 2)
    K, D, QB = 8, 5000, 512
    ks = jax.random.split(key, 3)
    buf = jax.random.normal(ks[0], (K, D), jnp.float32) * 0.1
    params = jax.random.normal(ks[1], (D,), jnp.float32)
    wvec = _wvec(mode, K, ks[2])

    kw = dict(server_lr=0.3, alpha=0.5, momentum=0.8, ema_anchor=0.05,
              backend="xla", block_d=1024)
    if wire == "q8":
        kw.update(quantized=True, qblock=QB)
        payload = _quantize(buf, D, QB)
    elif wire == "q4":
        kw.update(wire="q4", qblock=QB)
        payload = _q4_payload(buf, D, QB, key)
    elif wire == "topk":
        kw.update(wire="topk", qblock=64)
        payload = _topk_payload(buf, 512, 64)
    else:
        payload = buf

    single = agg.FlatServer(mode, D, **kw)
    hier = agg.FlatServer(mode, D, mesh=mesh, **kw)
    assert hier.traffic["cross_edge_reduction"] == 2.0
    p1, o1, m1 = single.step(jnp.array(params, copy=True), payload, wvec,
                             single.init_opt(params))
    psh = (tuple(shflat.shard_rows(a, mesh) for a in payload)
           if isinstance(payload, tuple)
           else shflat.shard_rows(payload, mesh))
    p2, o2, m2 = hier.step(jnp.array(params, copy=True), psh, wvec,
                           hier.init_opt(params))
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2),
                               atol=2e-5, rtol=2e-5)
    assert float(m1["update_norm"]) == pytest.approx(
        float(m2["update_norm"]), rel=1e-3, abs=1e-6)
    for a, c in zip(jax.tree_util.tree_leaves(o1),
                    jax.tree_util.tree_leaves(o2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=2e-5, rtol=2e-5)


@hier4
@pytest.mark.parametrize("mode", ["fedsgd", "fedavg", "fedasync", "sdga"])
def test_hier_server_q8_parity_in_int8dot_regime(mode, key):
    """K=64: the q8 reduction auto-dispatches to the int8-dot path at
    global K >= 32.  The coefficient-scale pmax must span BOTH mesh axes
    on the 2-D mesh — a pod-only pmax would pin different scales per
    edge group and the cross-edge psum would mix grids."""
    mesh = shflat.make_hier_mesh(2, 2)
    K, D, QB = 64, 5000, 512
    ks = jax.random.split(key, 3)
    buf = jax.random.normal(ks[0], (K, D), jnp.float32) * 0.1
    params = jax.random.normal(ks[1], (D,), jnp.float32)
    wvec = _wvec(mode, K, ks[2])
    q, s = _quantize(buf, D, QB)
    kw = dict(server_lr=0.3, alpha=0.5, momentum=0.8, ema_anchor=0.05,
              backend="xla", quantized=True, qblock=QB)
    single = agg.FlatServer(mode, D, **kw)
    hier = agg.FlatServer(mode, D, mesh=mesh, **kw)
    p1, _, m1 = single.step(jnp.array(params, copy=True), (q, s), wvec,
                            single.init_opt(params))
    qs = tuple(shflat.shard_rows(a, mesh) for a in (q, s))
    p2, _, m2 = hier.step(jnp.array(params, copy=True), qs, wvec,
                          hier.init_opt(params))
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2),
                               atol=2e-5, rtol=2e-5)
    assert float(m1["update_norm"]) == pytest.approx(
        float(m2["update_norm"]), rel=1e-3, abs=1e-6)


@multidevice
def test_alias_mesh_is_bitwise_the_pod_mesh(key):
    """mesh_shape=(1, P) returns the literal 1-D pod mesh, so the server
    round is bit-identical to the devices=P path — not merely close."""
    m1 = shflat.make_pod_mesh(2)
    ma = shflat.make_hier_mesh(1, 2)
    assert ma.axis_names == m1.axis_names == (shflat.POD_AXIS,)
    assert not shflat.is_hier(ma)
    K, D = 4, 3000
    ks = jax.random.split(key, 2)
    buf = jax.random.normal(ks[0], (K, D), jnp.float32) * 0.1
    params = jax.random.normal(ks[1], (D,), jnp.float32)
    w = jnp.ones((K,), jnp.float32)
    outs = []
    for mesh in (m1, ma):
        srv = agg.FlatServer("fedavg", D, server_lr=0.3, mesh=mesh)
        p, _, _ = srv.step(jnp.array(params, copy=True),
                           shflat.shard_rows(buf, mesh), w,
                           srv.init_opt(params))
        outs.append(np.asarray(p))
    np.testing.assert_array_equal(outs[0], outs[1])


@hier4
def test_hier_server_compile_count_stays_one(key):
    """ONE program per (mode, wire): rounds with fresh weight values (same
    shapes) must reuse the compiled hierarchical step — the tree reduce
    is traced inside the server program, not rebuilt per round."""
    mesh = shflat.make_hier_mesh(2, 2)
    K, D = 8, 2000
    srv = agg.FlatServer("fedbuff", D, server_lr=0.3, alpha=0.5, mesh=mesh)
    params = jax.device_put(jax.random.normal(key, (D,), jnp.float32),
                            shflat.replicated(mesh))
    opt = srv.init_opt(params)
    for r in range(4):
        buf = shflat.shard_rows(
            jax.random.normal(jax.random.fold_in(key, r), (K, D),
                              jnp.float32), mesh)
        wvec = jnp.asarray((np.arange(K) + r) % 5, jnp.float32)
        params, opt, _ = srv.step(params, buf, wvec, opt)
    CompileLog().track("hier_step", srv).assert_exactly("hier_step", 1)


# ---------------------- sharding-rules integration ----------------------


@hier4
def test_rules_batch_and_cache_specs_span_edge_axis():
    """The training-side data-parallel specs lay the batch over the
    flattened (edge, pod) axes, edge outermost, so wave lanes and KV/state
    caches follow the same row layout as the channel."""
    mesh = shflat.make_hier_mesh(2, 2)
    bs = rules.batch_spec(mesh)
    assert tuple(bs) == (("edge", "pod"),)
    cache = {"h": jnp.zeros((2, 8, 4, 16))}
    specs = rules.cache_specs(cache, mesh, batch=8)
    spec = jax.tree_util.tree_leaves(specs)[0].spec
    assert ("edge", "pod") in tuple(spec)


@multidevice
def test_rules_pod_only_mesh_specs_unchanged():
    """1-D meshes keep the pre-hierarchy bare-"pod" spec (cache keys and
    lowered programs stay byte-identical)."""
    mesh = shflat.make_pod_mesh(2)
    assert tuple(rules.batch_spec(mesh)) == ("pod",)


# ------------------------- engine-level parity -------------------------


@pytest.fixture(scope="module")
def setup():
    ds = make_dataset("sentiment140", n=400, seed=0)
    tr, te = train_test_split(ds)
    shards = build_client_shards(tr, "iid", n_clients=8, batch_size=8)
    p0, s0, apply_fn = build_lstm(jax.random.PRNGKey(0), "sentiment",
                                  embed=2, hidden=4)
    return shards, te, p0, s0, apply_fn


def _run(setup, rounds=4, **kw):
    shards, te, p0, s0, apply_fn = setup
    cfg = FLConfig(n_clients=8, k=4, mode="semi_async",
                   aggregation=kw.pop("aggregation", "fedsgd"),
                   client_lr=0.05, server_lr=0.05, target_accuracy=0.9,
                   **kw)
    eng = FLEngine(cfg, apply_fn, "sentiment", p0, s0, shards,
                   te.x[:32], te.y[:32])
    return eng.run(rounds), eng


@hier4
@pytest.mark.parametrize("channel", ["streaming", "buffered"])
def test_hier_engine_matches_single_device(setup, channel):
    """The 2-D-mesh batched engine runs the identical simulated schedule
    and reproduces the single-device numerics on both server channels."""
    r1, e1 = _run(setup, server_channel=channel)
    rh, eh = _run(setup, mesh_shape=(2, 2), server_channel=channel)
    assert rh.staleness_hist == r1.staleness_hist
    assert rh.metrics.total_tx_bytes() == r1.metrics.total_tx_bytes()
    np.testing.assert_allclose(np.asarray(eh._flat_params),
                               np.asarray(e1._flat_params),
                               atol=1e-4, rtol=1e-4)
    assert eh._server.traffic["mesh_shape"] == (2, 2)
    assert eh._server.traffic["cross_edge_reduction"] == 2.0


@hier4
def test_hier_engine_q8_streaming_matches_single_device(setup):
    r1, e1 = _run(setup, compress_updates=True)
    rh, eh = _run(setup, mesh_shape=(2, 2), compress_updates=True)
    assert rh.staleness_hist == r1.staleness_hist
    np.testing.assert_allclose(np.asarray(eh._flat_params),
                               np.asarray(e1._flat_params),
                               atol=5e-3, rtol=5e-3)


@hier4
def test_hier_engine_channel_lives_on_all_devices(setup):
    """Per-edge streaming accumulators: each of the E*P mesh shards owns
    its own AccumBuffer row (fold-at-edge), laid out across all devices."""
    _, eng = _run(setup, mesh_shape=(2, 2))
    assert eng._streaming and eng._accum is not None
    assert eng._accum._bank.shape[0] == 4
    assert len(eng._accum._bank.sharding.device_set) == 4
    _, enb = _run(setup, mesh_shape=(2, 2), server_channel="buffered")
    assert len(enb._buf.sharding.device_set) == 4


@multidevice
def test_alias_engine_is_bitwise_the_devices_engine(setup):
    """FLConfig(mesh_shape=(1, 2)) must be byte-identical to devices=2 at
    the engine level — same mesh object shape, same programs, same bits."""
    ra, ea = _run(setup, mesh_shape=(1, 2))
    rd, ed = _run(setup, devices=2)
    np.testing.assert_array_equal(np.asarray(ea._flat_params),
                                  np.asarray(ed._flat_params))


@pytest.mark.slow
def test_hier_parity_subprocess():
    """8-virtual-device hierarchy parity from a single-device session:
    (2, 4) and (4, 2) meshes vs the flat 8-device mesh vs single device,
    plus the (1, 8) alias bitwise vs devices=8."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.configs.base import FLConfig
        from repro.core import FLEngine
        from repro.data import (build_client_shards, make_dataset,
                                train_test_split)
        from repro.models.lstm import build_lstm
        ds = make_dataset("sentiment140", n=300, seed=0)
        tr, te = train_test_split(ds)
        shards = build_client_shards(tr, "iid", n_clients=16, batch_size=8)
        p0, s0, fn = build_lstm(jax.random.PRNGKey(0), "sentiment",
                                embed=2, hidden=4)
        def run(**kw):
            cfg = FLConfig(n_clients=16, k=8, mode="semi_async",
                           aggregation="fedsgd", client_lr=0.05,
                           server_lr=0.05, target_accuracy=0.9, **kw)
            eng = FLEngine(cfg, fn, "sentiment", p0, s0, shards,
                           te.x[:32], te.y[:32])
            eng.run(3)
            return np.asarray(eng._flat_params), eng
        f1, _ = run(devices=1)
        f8, _ = run(devices=8)
        for ms in [(2, 4), (4, 2)]:
            fh, eh = run(mesh_shape=ms)
            np.testing.assert_allclose(fh, f1, atol=1e-4, rtol=1e-4)
            np.testing.assert_allclose(fh, f8, atol=1e-4, rtol=1e-4)
            t = eh._server.traffic
            assert t["cross_edge_reduction"] == float(ms[1]), t
        fa, _ = run(mesh_shape=(1, 8))
        np.testing.assert_array_equal(fa, f8)
        print("HIER_PARITY_OK")
    """)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "HIER_PARITY_OK" in out.stdout, out.stderr[-2000:]
