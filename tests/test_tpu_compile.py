"""Compile-only guards: the server kernels at ResNet-18 width for a v5e.

Each test compiles one Pallas kernel the engine's ``FlatServer`` calls,
at D = 11,173,962 (ResNet-18, width 64) and K = 8 (K = 64 for the widest
quantized tiles), for a described (not attached) TPU v5e, and checks the
kernel survived as a ``tpu_custom_call``.  Nothing runs, so these catch
what interpret mode cannot: blocks the Mosaic tiling rules refuse, 1-D
dots it cannot lower, tiles past the scoped VMEM, kernels it cannot
partition over a mesh.  The topology is described in
a fixture, never at import: only the worker that runs this file loads
the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import aggregation as agg
from repro.kernels import safl_agg as kern
from repro.sharding import flat as shflat

D = 11_173_962  # ResNet-18 at width 64, 10 classes
K = 8
QB = 512
DQ = -(-D // QB) * QB
NB = DQ // QB
LR = 0.1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


_P = dict(interpret=False)
_Q = dict(interpret=False, qblock=QB)

# (name, kernel call, argument shapes): the engine-path server kernels
KERNELS = {
    "safl_fold": (lambda a, v: kern.safl_fold(a, v, 0.5, **_P),
                  [((D,),), ((D,),)]),
    "safl_fold_q8": (lambda a, q, s: kern.safl_fold_q8(a, q, s, 0.5, **_Q),
                     [((DQ,),), ((DQ,), jnp.int8), ((NB,),)]),
    "safl_fold_q4": (lambda a, q, s: kern.safl_fold_q4(a, q, s, 0.5, **_Q),
                     [((DQ,),), ((DQ // 2,), jnp.int8), ((NB,),)]),
    "safl_aggregate[fedsgd]": (
        lambda u, w, p: kern.safl_aggregate(u, w, p, LR, mode="fedsgd",
                                            **_P),
        [((K, D),), ((K,),), ((D,),)]),
    "safl_aggregate[avg]": (
        lambda u, w: kern.safl_aggregate(u, w, mode="avg", **_P),
        [((K, D),), ((K,),)]),
    "safl_aggregate[mix]": (
        lambda u, w, p: kern.safl_aggregate(u, w, p, mode="mix", **_P),
        [((K, D),), ((K,),), ((D,),)]),
    "safl_aggregate[sum]": (
        lambda u, w: kern.safl_aggregate(u, w, mode="sum", **_P),
        [((K, D),), ((K,),)]),
    "safl_aggregate_q8[sum]": (
        lambda q, s, w: kern.safl_aggregate_q8(q, s, w, mode="sum", **_Q),
        [((K, DQ), jnp.int8), ((K, NB),), ((K,),)]),
    "safl_aggregate_q4[sum]": (
        lambda q, s, w: kern.safl_aggregate_q4(q, s, w, mode="sum", **_Q),
        [((K, DQ // 2), jnp.int8), ((K, NB),), ((K,),)]),
    "sdga_aggregate": (
        lambda u, w, p, m, e: kern.sdga_aggregate(
            u, w, p, m, e, server_lr=LR, discount="none", **_P),
        [((K, D),), ((K,),), ((D,),), ((D,),), ((D,),)]),
    "screen_rows": (lambda u: kern.screen_rows(u, **_P), [((K, D),)]),
    "screen_rows_q8": (lambda q, s: kern.screen_rows_q8(q, s, **_Q),
                       [((K, DQ), jnp.int8), ((K, NB),)]),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    args = [_sds(one_chip, *s) for s in shapes]
    assert "tpu_custom_call" in _compile(fn, *args)


# The widest quantized tiles: FLConfig's coarsest quant_block (2048) makes
# FlatServer widen block_d to 8 scale rows = 16384 lanes, and a horizon
# holds up to K = 64 rows, so each grid step dequantizes a 4 MiB f32 tile
# (the q4 unpack holds several i32 temporaries of that size besides).
K_WIDE, QB_WIDE = 64, 2048
DQ_WIDE = -(-D // QB_WIDE) * QB_WIDE
NB_WIDE = DQ_WIDE // QB_WIDE


def _wide_cases(wire):
    bd = agg.FlatServer("fedsgd", D, server_lr=LR, backend="pallas",
                        wire=wire, qblock=QB_WIDE).block_d
    kq = dict(interpret=False, qblock=QB_WIDE, block_d=bd)
    nq = DQ_WIDE // (2 if wire == "q4" else 1)
    rows = [((K_WIDE, nq), jnp.int8), ((K_WIDE, NB_WIDE),)]
    vec = ((D,),)
    return bd, {
        "aggregate[fedsgd]": (
            lambda q, s, w, p: getattr(kern, f"safl_aggregate_{wire}")(
                q, s, w, p, LR, mode="fedsgd", **kq),
            rows + [((K_WIDE,),), vec]),
        "aggregate[sum]": (
            lambda q, s, w: getattr(kern, f"safl_aggregate_{wire}")(
                q, s, w, mode="sum", **kq),
            rows + [((K_WIDE,),)]),
        "sdga": (
            lambda q, s, w, p, m, e: getattr(kern, f"sdga_aggregate_{wire}")(
                q, s, w, p, m, e, server_lr=LR, discount="none", **kq),
            rows + [((K_WIDE,),), vec, vec, vec]),
        "fold": (
            lambda a, q, s: getattr(kern, f"safl_fold_{wire}")(
                a, q, s, 0.5, **kq),
            [((DQ_WIDE,),), ((nq,), jnp.int8), ((NB_WIDE,),)]),
        "screen": (
            lambda q, s: getattr(kern, f"screen_rows_{wire}")(q, s, **kq),
            rows),
    }


@pytest.mark.parametrize("wire", ["q8", "q4"])
@pytest.mark.parametrize("name", ["aggregate[fedsgd]", "aggregate[sum]",
                                  "sdga", "fold", "screen"])
def test_wide_quantized_tile_compiles_for_v5e(one_chip, wire, name):
    """K = 64 rows at quant_block 2048, with the tile FlatServer picks:
    the largest VMEM working set a quantized kernel can be given."""
    bd, cases = _wide_cases(wire)
    assert bd == 8 * QB_WIDE
    fn, shapes = cases[name]
    args = [_sds(one_chip, *s) for s in shapes]
    assert "tpu_custom_call" in _compile(fn, *args)


@pytest.mark.parametrize("mesh_shape", [(1, 4), (2, 2)],
                         ids=["pod4", "edge2x2"])
def test_mesh_fold_and_step_compile_for_v5e(topo, mesh_shape):
    """The streaming fold and the buffered step over a 4-chip mesh:
    Mosaic kernels cannot be partitioned automatically, so both must run
    their kernel inside ``shard_map`` (one bank row per chip)."""
    mesh = shflat.make_hier_mesh(*mesh_shape, devices=topo.devices)
    rows, rep = shflat.row_sharding(mesh), shflat.replicated(mesh)
    srv = agg.FlatServer("fedsgd", D, server_lr=LR, backend="pallas",
                         mesh=mesh, external_discount=True, donate=False)
    scalar = _sds(rep, ())
    fold = srv.fold_program.lower(
        _sds(rows, (4, D)), _sds(rep, (D,)), _sds(rep, (), jnp.int32),
        scalar, scalar).compile().as_text()
    assert "tpu_custom_call" in fold
    wrows = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
        rows.spec[0]))
    step = srv._fn.lower(_sds(rep, (D,)), _sds(rows, (K, D)),
                         _sds(wrows, (K,)), {}).compile().as_text()
    assert "tpu_custom_call" in step
