"""Quantized / sparse wire formats — the ONE quantizer home of the repo.

Grid over row tiles; each program quantizes a (ROWS, BLOCK) tile in VMEM:
scale_r = max|x_r|/127 per row, q = round(x/scale).  Used by the FL engines
to cut the paper's channel-transmission payload (beyond-paper, Table 2
axis); dequantize is the exact inverse mapping up to rounding.

``BLOCK`` (512) is the single quantization granule for the whole repo:
every wire format below shares it, and the fused dequant-aggregate
kernels in :mod:`repro.kernels.safl_agg` consume (K, D) int8 buffers
with one f32 scale per BLOCK lanes.

Wire formats (``FLConfig.wire``; per-upload bytes via
:func:`payload_nbytes`):

  * ``q8`` — int8 absmax rows, 1 byte/coord + 4 B scale per BLOCK
    (:func:`quantize_int8` / :func:`dequantize_int8`, ~3.9x vs f32).
  * ``q4`` — packed int4, two lanes per byte on the symmetric [-7, 7]
    grid with *stochastic rounding* (:func:`quantize_q4` /
    :func:`dequantize_q4`, ~7.9x vs f32).  The uniform draws must come
    from a counter-keyed PRNG (``fold_in(fold_in(key(seed), cid),
    upload_counter)`` — the :mod:`repro.sched.timing` jitter rule) so
    every engine path reproduces them bit-identically.
  * ``topk`` — top-|x| sparsification to (int32 index, int8 value)
    pairs with BLOCK-granule scales over the *compacted* value array
    (~5 bytes/kept coord; ~8x vs f32 at the default 10% density).

Ad-hoc pytree compression for the transmission-load studies
(:func:`quantize_pytree` / :func:`topk_sparsify`) lives here too — the
former ``repro.core.compression`` shim collapsed into this module.

Backend selection follows the :func:`repro.kernels.safl_agg.default_backend`
convention: with ``interpret=None`` (the default) the compiled Pallas kernel
runs on TPU and the jnp oracle (:mod:`repro.kernels.ref`) elsewhere;
``REPRO_AGG_BACKEND=pallas|pallas_interpret|xla`` overrides, and an explicit
``interpret`` bool forces the Pallas path as before.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

Pytree = Any

ROWS = 8
BLOCK = 512

WIRES = ("f32", "q8", "q4", "topk")


def payload_nbytes(wire: str, *, d: int, dq: int = 0, n_qblocks: int = 0,
                   nk: int = 0, nk_qblocks: int = 0) -> int:
    """Bytes ONE upload payload puts on the wire — the single byte-
    accounting rule every channel consumer (engine tx/rx meters,
    agg_bench columns) reads.

    f32: 4 B/coord over the raw d.  q8: 1 B/coord over the padded dq +
    4 B per scale block.  q4: half a byte per padded coord + the same
    scales.  topk: 4 B index + 1 B value per kept coord + 4 B per scale
    block of the compacted array.
    """
    assert wire in WIRES, wire
    if wire == "f32":
        return d * 4
    if wire == "q8":
        return dq + n_qblocks * 4
    if wire == "q4":
        return dq // 2 + n_qblocks * 4
    return nk * 5 + nk_qblocks * 4


def _resolve_backend(interpret: bool | None) -> str:
    """None -> platform auto-detect (safl_agg convention); bool -> Pallas."""
    if interpret is None:
        from repro.kernels.safl_agg import default_backend
        return default_backend()
    return "pallas_interpret" if interpret else "pallas"


# The per-row scales travel as an (R, 1) column: Mosaic refuses a rank-1
# (ROWS,) block that spans fewer than 128 rows.
def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)  # (ROWS, BLOCK)
    scale = jnp.maximum(
        jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def quantize_int8(x: jax.Array, rows: int = ROWS,
                  interpret: bool | None = None):
    """x (R, B) -> (q int8 (R,B), scales f32 (R,)).  R padded to rows."""
    backend = _resolve_backend(interpret)
    if backend == "xla":
        from repro.kernels import ref
        return ref.quantize_ref(x)
    R, B = x.shape
    pad = (-R) % rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    Rp = R + pad
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(Rp // rows,),
        in_specs=[pl.BlockSpec((rows, B), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((rows, B), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((Rp, B), jnp.int8),
                   jax.ShapeDtypeStruct((Rp, 1), jnp.float32)),
        interpret=backend == "pallas_interpret",
    )(x)
    return q[:R], s[:R, 0]


def dequantize_int8(q: jax.Array, scales: jax.Array, rows: int = ROWS,
                    interpret: bool | None = None) -> jax.Array:
    backend = _resolve_backend(interpret)
    if backend == "xla":
        from repro.kernels import ref
        return ref.dequantize_ref(q, scales)
    R, B = q.shape
    pad = (-R) % rows
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0)))
        scales = jnp.pad(scales, (0, pad))
    Rp = R + pad
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(Rp // rows,),
        in_specs=[pl.BlockSpec((rows, B), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, B), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, B), jnp.float32),
        interpret=backend == "pallas_interpret",
    )(q, scales[:, None])
    return out[:R]


# ---------------------------------------------------------------------------
# packed int4 with stochastic rounding (client-side; thin over the oracles —
# quantization is O(D) elementwise and fuses into the jitted client
# programs, so there is no standalone hot kernel to tile)
# ---------------------------------------------------------------------------


def quantize_q4(x: jax.Array, u: jax.Array):
    """x (R, B) f32 + u (R, B) uniform[0,1) draws -> (packed int8
    (R, B//2), scales f32 (R,)).  Blockwise absmax/7 grid, stochastic
    rounding (E[dequant] = x), two nibbles per byte — see
    :func:`repro.kernels.ref.quantize_q4_ref` / ``pack_q4_ref``."""
    from repro.kernels import ref
    q, s = ref.quantize_q4_ref(x, u)
    return ref.pack_q4_ref(q), s


def dequantize_q4(p: jax.Array, scales: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_q4`: (R, B//2) packed + (R,) scales ->
    (R, B) f32."""
    from repro.kernels import ref
    return ref.unpack_q4_ref(p).astype(jnp.float32) * scales[:, None]


# ---------------------------------------------------------------------------
# ad-hoc pytree compression + top-k sparsification (transmission-load
# studies; the engine hot path quantizes inside core.flatbuf.PytreeCodec)
# ---------------------------------------------------------------------------


def quantize_array(x: jax.Array, block: int = BLOCK):
    """x: any shape -> (q int8 (n_blocks, block), scales f32, orig shape),
    reshaped through the shared BLOCK granule."""
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % block
    flat = jnp.pad(flat, (0, pad))
    q, scales = quantize_int8(flat.reshape(-1, block))
    return q, scales, x.shape


def dequantize_array(q: jax.Array, scale: jax.Array, shape) -> jax.Array:
    flat = dequantize_int8(q, scale).reshape(-1)
    n = int(np.prod(shape))
    return flat[:n].reshape(shape)


def quantize_pytree(tree: Pytree):
    """Per-leaf :func:`quantize_array`; returns (quantized tree, wire
    bytes = 1 B/coord + 4 B per block scale)."""
    qs = jax.tree_util.tree_map(quantize_array, tree,
                                is_leaf=lambda x: isinstance(x, jax.Array)
                                or isinstance(x, np.ndarray))
    nbytes = sum(q.size + s.size * 4
                 for q, s, _ in jax.tree_util.tree_leaves(
                     qs, is_leaf=lambda t: isinstance(t, tuple)))
    return qs, int(nbytes)


def dequantize_pytree(qs) -> Pytree:
    return jax.tree_util.tree_map(
        lambda t: dequantize_array(*t), qs,
        is_leaf=lambda t: isinstance(t, tuple))


def topk_sparsify(x: jax.Array, frac: float = 0.05):
    """Keep the top-|x| ``frac`` of coordinates: -> (values f32, indices
    int32, orig shape).  The engine's wire-format counterpart
    (int8-quantized values + error feedback) lives in
    ``core.flatbuf.PytreeCodec.ravel_delta_topk``."""
    flat = x.reshape(-1).astype(jnp.float32)
    k = max(1, int(flat.size * frac))
    vals, idx = jax.lax.top_k(jnp.abs(flat), k)
    return flat[idx], idx.astype(jnp.int32), x.shape


def topk_restore(vals, idx, shape) -> jax.Array:
    n = int(np.prod(shape))
    return jnp.zeros((n,), vals.dtype).at[idx].set(vals).reshape(shape)


def topk_bytes(vals, idx) -> int:
    return int(vals.size * 4 + idx.size * 4)
