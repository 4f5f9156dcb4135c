"""The benchmark's work functions: model FLOPs from shapes against XLA's
own count at real width, and the fold's bytes per wire."""
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tinycell import harness  # noqa: E402

import work  # noqa: E402


CONFIGS = ["resnet18-cifar10", "vgg16-cifar10"]


@pytest.mark.parametrize("config", CONFIGS)
def test_forward_flops_match_xla_at_real_width(config):
    """XLA's cost analysis on the CPU counts the convolutions' in-bounds
    taps too, plus the elementwise work the model FLOPs leave out
    (BatchNorm, ReLU, pooling): at most 2% more."""
    import jax
    import jax.numpy as jnp

    cfg, ref = harness.find_config(config)
    params, state = jax.eval_shape(
        lambda k: ref.init(cfg, k), jax.random.PRNGKey(0))
    hw, ch = cfg["image_size"], cfg["in_channels"]
    x = jax.ShapeDtypeStruct((1, hw, hw, ch), jnp.float32)
    fwd = jax.jit(lambda p, s, x: ref.apply(cfg, p, s, x, False)[0])
    xla = fwd.lower(params, state, x).compile().cost_analysis()
    xla = xla[0] if isinstance(xla, list) else xla
    ours = ref.flops_forward(cfg)
    assert 1.0 <= xla["flops"] / ours <= 1.02, (xla["flops"], ours)


@pytest.mark.parametrize("config", CONFIGS)
def test_training_flops_are_three_forwards(config):
    """Forward plus backward (input and weight gradients) of one training
    step, as XLA counts it, is the configuration's ``flops_train`` (three
    forward passes) to within 5%: what ``work.round_flops`` uses."""
    import jax
    import jax.numpy as jnp

    cfg, ref = harness.find_config(config)
    params, state = jax.eval_shape(
        lambda k: ref.init(cfg, k), jax.random.PRNGKey(0))
    hw, ch = cfg["image_size"], cfg["in_channels"]
    x = jax.ShapeDtypeStruct((2, hw, hw, ch), jnp.float32)
    y = jax.ShapeDtypeStruct((2,), jnp.int32)

    def loss(p, s, x, y):
        logits, _ = ref.apply(cfg, p, s, x, True)
        return jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, y[:, None], -1)[:, 0])

    xla = jax.jit(jax.grad(loss)).lower(params, state, x, y).compile()
    xla = xla.cost_analysis()
    xla = xla[0] if isinstance(xla, list) else xla
    assert ref.flops_train(cfg) == 3 * ref.flops_forward(cfg)
    ratio = xla["flops"] / (2 * ref.flops_train(cfg))
    assert 0.95 <= ratio <= 1.05, ratio


@pytest.mark.parametrize("config,gflop,n_params", [
    ("resnet18-cifar10", 0.963718656, 11_173_962),
    ("vgg16-cifar10", 0.495676928, 15_240_906)])
def test_published_sizes(config, gflop, n_params):
    import jax
    import numpy as np

    cfg, ref = harness.find_config(config)
    assert math.isclose(ref.flops_forward(cfg) / 1e9, gflop)
    params, _ = jax.eval_shape(lambda k: ref.init(cfg, k),
                               jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(params)) == cfg["n_params"] \
        == n_params


def test_round_flops_counts_training_and_eval():
    cell = harness.find_cell("resnet18.as-f32")
    fwd = cell["ref"].flops_forward(cell["cfg"])
    # 8 uploads x 128 samples x (forward + backward), plus 1,024 evals
    want = 8 * 128 * 3 * fwd + 1024 * fwd
    got = work.round_flops(cell["cfg"], cell["ref"], cell["traffic"])
    assert math.isclose(got, want)


@pytest.mark.parametrize("wire,d,qblock,want", [
    ("f32", 11_173_962, 512, 12 * 11_173_962),
    # 21,825 blocks of 512: accumulator read + write (f32), int8 codes,
    # one f32 scale per block
    ("q8", 11_173_962, 512, 8 * 11_174_400 + 11_174_400 + 4 * 21_825),
    ("q8", 1024, 512, 8 * 1024 + 1024 + 8),
])
def test_fold_bytes(wire, d, qblock, want):
    assert work.fold_bytes(d, wire, qblock) == want


def test_fold_bytes_unknown_wire():
    with pytest.raises(ValueError):
        work.fold_bytes(1024, "q4", 512)
