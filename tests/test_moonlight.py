"""The DeepSeek-V3 block (Moonlight-16B-A3B) and federated LoRA through
``FLEngine``, on the CPU at tiny widths, against the benchmark's plain
reference (``bench/configs/moonlight-16b-a3b-ep8.py``, which imports
nothing of the program):

* latent attention, the sigmoid router and the dropless held-expert
  layer, each against the reference equations; a skewed router drops
  nothing; the eight shares of an expert-parallel layer add up to the
  uncut layer;
* the whole model's loss and adapter gradients;
* the wave takes the frozen base as one unbatched argument, lowers no
  constant of its size, computes no weight gradient of it, and runs one
  grouped product over every lane's tokens;
* batched engine rounds equal the sequential path;
* the program's configuration and the benchmark's agree on every width.
"""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.configs.moonlight_16b_a3b_ep8 import CONFIG
from repro.core import FLEngine, flatbuf
from repro.core import client as cl
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models.transformer import fl_model, lora_apply, lora_init

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(ROOT, "bench", "configs",
                          "moonlight-16b-a3b-ep8.json")
FIXTURE = os.path.join(ROOT, "tests", "bench", "fixtures",
                       "tiny-moonlight.json")


def _reference():
    spec = importlib.util.spec_from_file_location(
        "moonlight_reference", os.path.join(
            ROOT, "bench", "configs", "moonlight-16b-a3b-ep8.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
TINY = json.load(open(FIXTURE))
OVERRIDES = {k: v for k, v in TINY["program_kwargs"].items()
             if k != "config"}
PCFG = dataclasses.replace(CONFIG, **OVERRIDES)


def _apply(params, state, x, train, frozen):
    return lora_apply(params, state, x, train, frozen=frozen,
                      config="moonlight_16b_a3b_ep8", **OVERRIDES)


def _weights(seed=0, perturb=0.05):
    """The reference's weights with the adapters moved off B = 0, so
    every adapter leaf has a gradient."""
    ad, st, base = REF.init(TINY, jax.random.PRNGKey(seed))
    ad = jax.tree_util.tree_map(
        lambda a: a + perturb * jax.random.normal(
            jax.random.PRNGKey(seed + 1), a.shape), ad)
    return ad, st, base


def _layer(tree, name, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree[name])


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a), np.asarray(b)
    scale = max(float(np.max(np.abs(b))), 1e-6)
    assert float(np.max(np.abs(a - b))) <= tol * scale, (
        float(np.max(np.abs(a - b))), scale)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_mla_matches_the_reference():
    ad, _, base = _weights()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, TINY["hidden_size"]))
    for i in range(2):
        name = "layers_dense" if i == 0 else "layers_moe"
        p, a = _layer(base, name, 0)["attn"], _layer(ad, name, 0)
        got = mla_lib.mla_attention(p, PCFG, x, jnp.arange(16), a)
        _close(got, REF._attention(TINY, p, a, x))


def _tokens(n=96, seed=4):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (n, TINY["hidden_size"]))


def _program_moe(p, h, offset=0, cfg=TINY):
    sel, w = moe_lib.sigmoid_route(h, p["router"], p["bias"],
                                   cfg["num_experts_per_tok"],
                                   cfg["routed_scaling_factor"])
    return moe_lib.held_experts(h, sel, w, p["w1"], p["w3"], p["w2"],
                                offset), sel


def test_router_and_held_experts_match_the_reference():
    _, _, base = _weights()
    p = _layer(base, "layers_moe", 1)["moe"]
    h = _tokens()
    sel_p, w_p = moe_lib.sigmoid_route(h, p["router"], p["bias"], 6, 2.446)
    sel_r, w_r = REF.route(TINY, h, p["router"], p["bias"])
    np.testing.assert_array_equal(np.asarray(sel_p), np.asarray(sel_r))
    _close(w_p, w_r)
    np.testing.assert_allclose(np.asarray(w_p).sum(-1), 2.446, rtol=1e-5)
    got, _ = _program_moe(p, h)
    _close(got, REF.routed_experts(TINY, p, h))


def test_a_skewed_router_drops_no_token():
    """A bias that puts expert 3 in every token's choice, and one held
    expert's router column scaled up: every token reaches expert 3, and
    the output still equals the capacity-free reference."""
    _, _, base = _weights()
    p = dict(_layer(base, "layers_moe", 0)["moe"])
    p["bias"] = p["bias"].at[3].set(10.0)
    p["router"] = p["router"].at[:, 5].multiply(8.0)
    h = _tokens(128)
    got, sel = _program_moe(p, h)
    assert bool(jnp.all(jnp.any(sel == 3, axis=-1)))
    load = moe_lib.held_load(sel, 8, 0)
    assert float(load[3]) == 1.0 and float(load.max()) == 1.0
    assert float(load[3] / load.sum()) > 0.3  # far above 1/8
    _close(got, REF.routed_experts(TINY, p, h))


def test_eight_shares_add_up_to_the_uncut_layer():
    """Each of the 8 chips of the deployment holds 8 of the 64 experts;
    their routed parts, with the shared experts counted once, add up to
    the layer that computes all 64."""
    full = dict(TINY, n_experts_held=64)
    _, _, base = REF.init(full, jax.random.PRNGKey(7))
    p = _layer(base, "layers_moe", 0)["moe"]
    h = _tokens()
    uncut = (REF.routed_experts(full, p, h, offset=0)
             + REF._swiglu(p["shared"], h))
    total = REF._swiglu(p["shared"], h)
    for chip in range(8):
        lo = 8 * chip
        share = dict(p, w1=p["w1"][lo:lo + 8], w3=p["w3"][lo:lo + 8],
                     w2=p["w2"][lo:lo + 8])
        part, _ = _program_moe(share, h, offset=lo)
        total = total + part
    _close(total, uncut)


def _ce(logits, y):
    return jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, y[..., None], -1)[..., 0])


def test_whole_model_loss_and_adapter_gradients():
    ad, st, base = _weights()
    x = jax.random.randint(jax.random.PRNGKey(5), (3, TINY["seq_len"]), 0,
                           TINY["vocab_size"])
    y = jnp.roll(x, -1, axis=1)

    def loss_p(a):
        return _ce(_apply(a, st, x, True, base)[0], y)

    def loss_r(a):
        return _ce(REF.apply(TINY, a, st, x, True, frozen=base)[0], y)

    lp, gp = jax.value_and_grad(loss_p)(ad)
    lr, gr = jax.value_and_grad(loss_r)(ad)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        assert float(jnp.max(jnp.abs(b))) > 0
        _close(a, b, 1e-4)


def test_training_the_held_experts_is_rejected():
    """``held_experts`` computes no gradient of the experts' weights, so
    full training of a sigmoid-router model fails at trace time instead
    of leaving its experts untrained; its router and every input still
    get gradients."""
    model = fl_model("moonlight_16b_a3b_ep8", **OVERRIDES)
    base = model.init(jax.random.PRNGKey(1))
    x = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0,
                           TINY["vocab_size"])
    batch = {"tokens": x}
    with pytest.raises(NotImplementedError, match="frozen"):
        jax.grad(lambda p: model.train_loss(p, batch)[0])(base)
    moe = _layer(base, "layers_moe", 0)["moe"]
    h = jax.random.normal(jax.random.PRNGKey(4), (16, PCFG.d_model))
    sel, w = moe_lib.sigmoid_route(h, moe["router"], moe["bias"],
                                   PCFG.top_k, PCFG.routed_scaling_factor)
    with pytest.raises(NotImplementedError, match="frozen"):
        jax.grad(lambda w1: moe_lib.held_experts(
            h, sel, w, w1, moe["w3"], moe["w2"]).sum())(moe["w1"])

    def through_router(r, h):
        s2, w2 = moe_lib.sigmoid_route(h, r, moe["bias"], PCFG.top_k,
                                       PCFG.routed_scaling_factor)
        return moe_lib.held_experts(h, s2, w2, moe["w1"], moe["w3"],
                                    moe["w2"]).sum()

    gr, gh = jax.grad(through_router, argnums=(0, 1))(moe["router"], h)
    assert float(jnp.max(jnp.abs(gr))) > 0
    assert float(jnp.max(jnp.abs(gh))) > 0


def test_adapters_are_the_only_trained_part():
    """The program's own initialisation: 263,168 adapter values a layer
    at the published widths (5 layers: the 1,315,840 uploaded), B = 0 so
    a fresh adapter leaves the base's logits as they are."""
    shapes = jax.eval_shape(lambda k: lora_init(CONFIG, k),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == 5 * 263_168 == 1_315_840
    model = fl_model("moonlight_16b_a3b_ep8", **OVERRIDES)
    base = model.init(jax.random.PRNGKey(1))
    ad = lora_init(model.cfg, jax.random.PRNGKey(2))
    x = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, 512)
    plain = model.forward(base, {"tokens": x})[0]
    _close(_apply(ad, {}, x, True, base)[0], plain)


# ---------------------------------------------------------------------------
# the wave: one unbatched frozen argument, no weight gradient, lanes merged
# ---------------------------------------------------------------------------


LANES = 5


def _wave_inputs():
    ad, st, base = _weights()
    codec = flatbuf.PytreeCodec(ad)
    n, nb, b, t = 4, 2, 2, TINY["seq_len"]
    xs = jax.random.randint(jax.random.PRNGKey(8), (n, nb, b, t), 0, 512)
    flat = jnp.stack([codec.ravel(ad)] * LANES)
    args = (flat, {}, xs, jnp.roll(xs, -1, -1), jnp.ones((n, nb, b)),
            jnp.arange(LANES), 0.05, base)
    wave = cl.make_batched_hetero_train(_apply, "token", "params", 1, codec)
    return wave, args, base


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def test_the_wave_takes_the_frozen_base_once_unbatched():
    wave, args, base = _wave_inputs()
    leaves = jax.tree_util.tree_leaves(base)
    text = wave.lower(*args).as_text()
    biggest = max(int(np.prod(a.shape)) for a in leaves)
    # no constant as large as a base leaf is compiled into the program
    for line in text.splitlines():
        if "stablehlo.constant" in line and "dense<" in line:
            assert f"{biggest}" not in line
    jaxpr = jax.make_jaxpr(wave)(*args).jaxpr
    # a weight matrix of the base with a lane axis in front (the norms'
    # scales are left out: at these widths activations share their shapes)
    lane_shapes = {(LANES,) + tuple(a.shape) for a in leaves
                   if a.size >= 1024}
    for e in _eqns(jaxpr):
        for v in e.outvars:
            assert tuple(getattr(v.aval, "shape", ())) not in lane_shapes, (
                "a base leaf broadcast per lane", e.primitive)


def _ragged(jaxpr):
    return [e for e in _eqns(jaxpr)
            if e.primitive.name.startswith("ragged_dot")]


def test_one_grouped_product_sees_every_lane_and_no_weight_gradient():
    wave, args, _ = _wave_inputs()
    eqns = _ragged(jax.make_jaxpr(wave)(*args).jaxpr)
    k = TINY["num_experts_per_tok"]
    rows = LANES * 2 * TINY["seq_len"] * k  # every lane's routed slots
    n_moe = TINY["num_hidden_layers"] - 1
    assert eqns
    for e in eqns:
        lhs, rhs = (v.aval for v in e.invars[:2])
        assert lhs.shape[0] == rows, lhs.shape  # lanes merged
        assert rhs.ndim == 3 and rhs.shape[0] == TINY["n_experts_held"]
        # the forward form only: a weight gradient would contract the
        # rows (lhs ragged in a contracting dimension), or emit (E, a, b)
        dn = e.params["ragged_dot_dimension_numbers"]
        assert list(dn.lhs_ragged_dimensions) == [0]
        assert list(dn.rhs_group_dimensions) == [0]
        assert e.outvars[0].aval.shape[0] == rows
    # forward 3 products, backward 3 recomputed and 3 transposed, in the
    # scanned MoE layer body (traced once)
    assert len(eqns) == 9, (len(eqns), n_moe)


def test_held_experts_merge_lanes_exactly():
    _, _, base = _weights()
    p = _layer(base, "layers_moe", 0)["moe"]
    hs = jax.random.normal(jax.random.PRNGKey(9), (3, 40, TINY["hidden_size"]))
    dys = jax.random.normal(jax.random.PRNGKey(10), hs.shape)

    def one(h, dy):
        def f(h):
            return jnp.sum(_program_moe(p, h)[0] * dy)
        return jax.value_and_grad(f)(h)

    batched = jax.vmap(one)(hs, dys)
    for i in range(3):
        v, g = one(hs[i], dys[i])
        _close(batched[0][i], v)
        _close(batched[1][i], g)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


def _engine(batch_clients: bool, seed=11):
    ad, st, base = REF.init(TINY, jax.random.PRNGKey(seed))
    n, per, b, t = 6, 4, 2, TINY["seq_len"]
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(seed + 1),
                                        (n * per + 4, t + 1), 0, 512))
    shards = [dict(xs=seq[i * per:(i + 1) * per, :-1].reshape(2, b, t),
                   ys=seq[i * per:(i + 1) * per, 1:].reshape(2, b, t),
                   mask=np.ones((2, b), np.float32), n=per)
              for i in range(n)]
    fl = FLConfig(mode="semi_async", aggregation="fedavg", k=3,
                  n_clients=n, local_epochs=1, local_batch_size=b,
                  client_lr=0.05, server_lr=1.0, eval_every=1,
                  batch_clients=batch_clients, seed=3)
    return FLEngine(fl, _apply, "token", ad, st, shards, seq[-4:, :-1],
                    seq[-4:, 1:], frozen=base)


def test_batched_rounds_equal_the_sequential_path():
    batched, seq = _engine(True), _engine(False)
    rb, rs = batched.run(4), seq.run(4)
    for a, b in zip(jax.tree_util.tree_leaves(rb.final_params),
                    jax.tree_util.tree_leaves(rs.final_params)):
        _close(a, b, 1e-4)
    lb = [r.loss for r in rb.metrics.records]
    ls = [r.loss for r in rs.metrics.records]
    np.testing.assert_allclose(lb, ls, rtol=1e-5)
    # the routed-load counter the model reports rides both paths' records
    for r in rb.metrics.records + rs.metrics.records:
        assert set(r.reported) == {"routed_held", "routed_max_share"}
        assert 0 < r.reported["routed_held"] <= TINY["num_experts_per_tok"]
        assert 1 / 8 <= r.reported["routed_max_share"] <= 1
    np.testing.assert_allclose(
        [r.reported["routed_held"] for r in rb.metrics.records],
        [r.reported["routed_held"] for r in rs.metrics.records], rtol=1e-5)


def test_the_engine_keeps_the_frozen_base_out_of_every_row():
    eng = _engine(True)
    eng.run(2)
    n_ad = sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(eng.global_params))
    assert eng.codec.d == n_ad  # the uploaded row is the adapters only
    assert all(f.shape == (n_ad,) for f in eng._client_flats)
    snap = eng._snapshot_tree()
    sizes = [int(np.prod(np.shape(a))) for a in
             jax.tree_util.tree_leaves(snap)]
    biggest = max(int(np.prod(a.shape)) for a in
                  jax.tree_util.tree_leaves(eng.frozen))
    assert max(sizes) < biggest


def test_routed_load_reaches_the_registry():
    from repro.obs import from_engine

    eng = _engine(True)
    eng.run(2)
    reg = from_engine(eng).to_json()
    held = reg["safl_model_routed_held"]["samples"][0]["value"]
    assert held == pytest.approx(
        eng.metrics.records[-1].reported["routed_held"])
    assert 0 < reg["safl_model_routed_max_share"]["samples"][0]["value"] <= 1


def test_the_token_loss_masks_whole_samples():
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 7))
    y = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, 7)
    loss = cl.make_loss_fn(lambda p, s, x, t: (logits, s), "token")
    full, _ = loss({}, {}, None, y, jnp.ones((2,)))
    first, _ = loss({}, {}, None, y, jnp.array([1.0, 0.0]))
    assert float(full) == pytest.approx(float(cl.sequence_loss(logits, y)))
    assert float(first) == pytest.approx(
        float(cl.sequence_loss(logits[:1], y[:1])))


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------


def test_program_and_benchmark_agree_on_every_width():
    b = json.load(open(BENCH_JSON))
    c = CONFIG
    pairs = {
        "hidden_size": c.d_model, "intermediate_size": c.d_ff_dense,
        "moe_intermediate_size": c.d_ff, "kv_lora_rank": c.kv_lora_rank,
        "qk_nope_head_dim": c.qk_nope_head_dim,
        "qk_rope_head_dim": c.qk_rope_head_dim,
        "v_head_dim": c.v_head_dim, "num_attention_heads": c.n_heads,
        "num_key_value_heads": c.n_kv_heads,
        "num_hidden_layers": c.n_layers, "vocab_size": c.vocab_size,
        "n_routed_experts": c.n_experts, "num_experts_per_tok": c.top_k,
        "n_shared_experts": c.n_shared_experts,
        "first_k_dense_replace": c.first_k_dense,
        "routed_scaling_factor": c.routed_scaling_factor,
        "rms_norm_eps": c.norm_eps, "rope_theta": c.rope_theta,
        "n_experts_held": c.n_experts_held,
        "held_expert_offset": c.held_expert_offset,
        "lora_rank": c.lora_rank, "lora_alpha": c.lora_alpha,
        "lora_targets": list(c.lora_targets),
        "tie_word_embeddings": c.tie_embeddings,
    }
    for key, val in pairs.items():
        assert b[key] == val, (key, b[key], val)
    assert b["scoring_func"] == "sigmoid" and c.router == "sigmoid"
    assert b["q_lora_rank"] is None and c.attn_type == "mla"
    assert c.source == b["source"]
    assert b["program_kwargs"] == {"config": "moonlight_16b_a3b_ep8"}
    assert c.padded_vocab == c.vocab_size  # no padded ids in the logits


def test_kimi_dense_layer_takes_its_own_width():
    from repro.configs import ARCHS, reduced_config
    from repro.models import build_model

    kimi = ARCHS["kimi-k2-1t-a32b"]
    assert kimi.d_ff_dense == 18_432 and kimi.dense_ff == 18_432
    assert kimi.source.startswith("https://huggingface.co/moonshotai/")
    cfg = reduced_config(kimi)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    dense = shapes["layers_dense"]["mlp"]["w1"].shape
    experts = shapes["layers_moe"]["moe"]["w1"].shape
    assert dense[-1] == cfg.d_ff_dense != cfg.d_ff == experts[-1]
