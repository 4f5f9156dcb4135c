"""ResNet-18 (CIFAR variant) for the benchmark: data and weights from a
seed, the plain reference forward pass, and the model FLOPs from shapes.

Nothing here imports the program.  ``init`` lays the weights out in the
dict layout the program's ``resnet18_apply`` reads (``stem``, ``bn0``,
``s{stage}b{block}`` with ``c1``/``bn1``/``c2``/``bn2`` and, where the
block changes shape, ``down``/``bnd``; ``fc``/``fcb``), so the program
and the reference start from the same numbers.

The reference follows He et al. (arXiv:1512.03385) with the CIFAR stem
the paper's section 4.3.2 uses: a 3x3 stride-1 convolution and no max
pool, BatchNorm after every convolution (batch statistics in training,
running statistics in evaluation, running averages with momentum
``bn_momentum``), ReLU, identity shortcuts or a 1x1 projection where the
shape changes, global average pooling and one dense layer.
"""
from __future__ import annotations

import os
import sys

import numpy as np

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

# the inputs and targets: synthetic CIFAR-10 (``bench/cifar.py``)
from cifar import make_data  # noqa: E402,F401


def _blocks(cfg):
    """(name, cin, cout, stride) of every basic block, in order."""
    w = cfg["width"]
    out, cin = [], w
    for si, (n, stride) in enumerate(zip(cfg["stage_blocks"],
                                         cfg["stage_strides"])):
        cout = w * 2 ** si
        for bi in range(n):
            st = stride if bi == 0 else 1
            out.append((f"s{si}b{bi}", cin, cout, st))
            cin = cout
    return out


def init(cfg, key):
    """(params, state): He-normal convolutions and dense layers, BatchNorm
    scale 1, bias 0, running mean 0, variance 1.  Jittable."""
    import jax
    import jax.numpy as jnp

    blocks = _blocks(cfg)
    keys = iter(jax.random.split(key, 3 * len(blocks) + 2))

    def he(shape):
        fan_in = int(np.prod(shape[:-1]))
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * np.float32(np.sqrt(2.0 / fan_in)))

    def bn(c):
        return ({"scale": jnp.ones((c,), jnp.float32),
                 "bias": jnp.zeros((c,), jnp.float32)},
                {"mean": jnp.zeros((c,), jnp.float32),
                 "var": jnp.ones((c,), jnp.float32)})

    w = cfg["width"]
    params, state = {"stem": he((3, 3, cfg["in_channels"], w))}, {}
    params["bn0"], state["bn0"] = bn(w)
    for name, cin, cout, st in blocks:
        p, s = {}, {}
        p["c1"] = he((3, 3, cin, cout))
        p["bn1"], s["bn1"] = bn(cout)
        p["c2"] = he((3, 3, cout, cout))
        p["bn2"], s["bn2"] = bn(cout)
        if st != 1 or cin != cout:
            p["down"] = he((1, 1, cin, cout))
            p["bnd"], s["bnd"] = bn(cout)
        params[name], state[name] = p, s
    cin = blocks[-1][2]
    params["fc"] = he((cin, cfg["n_classes"]))
    params["fcb"] = jnp.zeros((cfg["n_classes"],), jnp.float32)
    return params, state


def apply(cfg, params, state, x, train, frozen=None):
    """Plain forward pass: (logits, new running statistics).  Nothing is
    frozen: every weight is trained."""
    import jax
    import jax.numpy as jnp

    mom, eps = cfg["bn_momentum"], cfg["bn_eps"]

    def conv(h, w, stride=1):
        return jax.lax.conv_general_dilated(
            h, w.astype(h.dtype), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def bn(p, s, h):
        if train:
            mean = jnp.mean(h, axis=(0, 1, 2))
            var = jnp.mean(jnp.square(h - mean), axis=(0, 1, 2))
            new = {"mean": mom * s["mean"] + (1 - mom) * mean,
                   "var": mom * s["var"] + (1 - mom) * var}
        else:
            mean, var, new = s["mean"], s["var"], s
        y = (h - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
        return y.astype(h.dtype), new

    news = {}
    h, news["bn0"] = bn(params["bn0"], state["bn0"],
                        conv(x, params["stem"]))
    h = jax.nn.relu(h)
    for name, _cin, _cout, st in _blocks(cfg):
        p, s = params[name], state[name]
        ns = {}
        y, ns["bn1"] = bn(p["bn1"], s["bn1"], conv(h, p["c1"], st))
        y = jax.nn.relu(y)
        y, ns["bn2"] = bn(p["bn2"], s["bn2"], conv(y, p["c2"]))
        if "down" in p:
            h, ns["bnd"] = bn(p["bnd"], s["bnd"], conv(h, p["down"], st))
        h = jax.nn.relu(y + h)
        news[name] = ns
    h = jnp.mean(h, axis=(1, 2))
    return h @ params["fc"].astype(h.dtype) + params["fcb"], news


def _conv_flops(hw_in, stride, k, cin, cout):
    """Multiply-adds x 2 of a SAME convolution, counting only the taps
    that land inside the input (zero padding is not work)."""
    hw_out = -(-hw_in // stride)
    pad = max((hw_out - 1) * stride + k - hw_in, 0) // 2
    taps = 0
    for o in range(hw_out):
        lo = o * stride - pad
        taps += sum(1 for t in range(k) if 0 <= lo + t < hw_in)
    return 2 * taps * taps * cin * cout, hw_out


def flops_forward(cfg):
    """Model FLOPs of one sample's forward pass: convolutions and the
    dense head (BatchNorm, ReLU and pooling are not counted)."""
    hw = cfg["image_size"]
    total, hw = _conv_flops(hw, 1, 3, cfg["in_channels"], cfg["width"])
    for _name, cin, cout, st in _blocks(cfg):
        f1, hw_out = _conv_flops(hw, st, 3, cin, cout)
        f2, _ = _conv_flops(hw_out, 1, 3, cout, cout)
        total += f1 + f2
        if st != 1 or cin != cout:
            total += _conv_flops(hw, st, 1, cin, cout)[0]
        hw = hw_out
    return float(total + 2 * _blocks(cfg)[-1][2] * cfg["n_classes"])


def flops_train(cfg):
    """Model FLOPs of one sample's training step: forward, input
    gradients and weight gradients, three forward passes' worth."""
    return 3.0 * flops_forward(cfg)
