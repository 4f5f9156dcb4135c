"""VGG-16 (configuration D) with the 32x32 head, for the benchmark: data
and weights from a seed, the plain reference forward pass, and the model
FLOPs from shapes.

Nothing here imports the program.  ``init`` lays the weights out in the
dict layout the program's ``vgg16_apply`` reads (``c0``..``c12`` for the
convolutions, ``f1``/``fb1``, ``f2``/``fb2``, ``f3``/``fb3`` for the
head).  The reference follows Simonyan and Zisserman (arXiv:1409.1556),
configuration D: 3x3 stride-1 SAME convolutions with ReLU, 2x2 max
pools after each stage, no BatchNorm; the head is the SAFL paper's
512-512-10 for 32x32 inputs (section 4.3.3) in place of 4096-4096-1000.
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


def _convs(cfg):
    """(cin, cout, hw) of each convolution, and the final (hw, channels)."""
    out, cin, hw = [], cfg["in_channels"], cfg["image_size"]
    for item in cfg["plan"]:
        if item == "M":
            hw //= 2
        else:
            out.append((cin, item, hw))
            cin = item
    return out, hw, cin


def init(cfg, key):
    """(params, {}): He-normal weights, zero biases.  Jittable."""
    import jax
    import jax.numpy as jnp

    convs, hw, cin = _convs(cfg)
    keys = iter(jax.random.split(key, len(convs) + 3))

    def he(shape):
        fan_in = int(np.prod(shape[:-1]))
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * np.float32(np.sqrt(2.0 / fan_in)))

    params = {f"c{i}": he((3, 3, a, b)) for i, (a, b, _) in enumerate(convs)}
    feat = hw * hw * cin
    dims = [feat] + list(cfg["head"]) + [cfg["n_classes"]]
    for j in range(len(dims) - 1):
        params[f"f{j + 1}"] = he((dims[j], dims[j + 1]))
        params[f"fb{j + 1}"] = jnp.zeros((dims[j + 1],), jnp.float32)
    return params, {}


def apply(cfg, params, state, x, train, frozen=None):
    """Plain forward pass: (logits, state unchanged: VGG has no BN).
    Nothing is frozen: every weight is trained."""
    import jax
    import jax.numpy as jnp

    del train
    h, i = x, 0
    for item in cfg["plan"]:
        if item == "M":
            n, hh, ww, c = h.shape
            h = jnp.max(h.reshape(n, hh // 2, 2, ww // 2, 2, c), axis=(2, 4))
        else:
            h = jax.nn.relu(jax.lax.conv_general_dilated(
                h, params[f"c{i}"].astype(h.dtype), (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC")))
            i += 1
    h = h.reshape(h.shape[0], -1)
    n_dense = len(cfg["head"]) + 1
    for j in range(1, n_dense + 1):
        h = h @ params[f"f{j}"].astype(h.dtype) + params[f"fb{j}"]
        if j < n_dense:
            h = jax.nn.relu(h)
    return h, state


def _taps(hw, k=3):
    """In-bounds taps of a k-wide SAME stride-1 window along one axis."""
    pad = (k - 1) // 2
    return sum(1 for o in range(hw) for t in range(k)
               if 0 <= o - pad + t < hw)


def flops_forward(cfg):
    """Model FLOPs of one sample's forward pass: convolutions (in-bounds
    taps only: zero padding is not work) and the dense head."""
    convs, hw, cin = _convs(cfg)
    total = sum(2 * _taps(h) ** 2 * a * b for a, b, h in convs)
    dims = [hw * hw * cin] + list(cfg["head"]) + [cfg["n_classes"]]
    total += sum(2 * a * b for a, b in zip(dims, dims[1:]))
    return float(total)


def flops_train(cfg):
    """Model FLOPs of one sample's training step: forward, input
    gradients and weight gradients, three forward passes' worth."""
    return 3.0 * flops_forward(cfg)
