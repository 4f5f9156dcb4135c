"""Pod-axis / (edge, pod) sharding for the flat (K, D) SAFL channel.

The batched SAFL engine keeps every client upload as a row of one flat
(K, D) device buffer (f32 :class:`repro.core.flatbuf.PytreeCodec` layout or
the int8+scales :class:`repro.core.flatbuf.QuantBuffer`).  Both halves of
the hot path scale along that same leading K axis:

  * the vmapped heterogeneous *wave* (one lane per buffered client
    training) is data-parallel over clients, and
  * the server round is a K-way weighted reduction.

So multi-device SAFL is ONE sharding decision: lay the K rows out over the
device mesh.  Two topologies:

  * **1-D "pod" mesh** (``FLConfig.devices``, :func:`make_pod_mesh`): rows
    split ``P("pod", None)``, the server reduction is a per-shard partial
    weighted sum plus ONE global ``psum`` over pod links
    (:func:`podwise_sums`).
  * **2-D (edge, pod) mesh** (``FLConfig.mesh_shape=(E, P)``,
    :func:`make_hier_mesh`): the hierarchical topology real FL deployments
    run (clients -> edge aggregators -> central server).  Rows split over
    the *flattened* ``("edge", "pod")`` axes (device (e, p) owns row block
    e*P + p), per-shard partials first tree-reduce *within* an edge group
    — log2(P) recursive-doubling ``ppermute`` rounds over the pod
    sub-axis (:func:`repro.kernels.safl_agg.edge_partial_reduce`) — and
    only the E edge partials cross the edge boundary, in ONE ``psum``
    over the edge axis.  Cross-edge traffic drops by a factor of P vs the
    flat global psum (:func:`edge_traffic` is the byte model), and no
    single device ever materializes more than its edge's rows.
    ``mesh_shape=(1, P)`` is the exact ``devices=P`` alias: E == 1 builds
    the plain 1-D pod mesh, so the alias path is bit-identical.

Everything here is layout only — no numerics.  The per-shard partial
reduction body is injected by the caller
(:class:`repro.core.aggregation.FlatServer` passes the Pallas ``mode="sum"``
kernel on TPU and the jnp / streaming-q8 references on CPU), so backend
selection stays in one place; for the q8/q4 wires that per-shard body
dequantizes *before* the tree reduce, so edge partials are always f32 and
the 1-D parity tolerances carry over unchanged.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

POD_AXIS = "pod"
EDGE_AXIS = "edge"


def make_pod_mesh(n_devices: int, devices=None) -> Mesh:
    """1-D mesh over the first ``n_devices`` local devices, axis "pod".

    On CPU hosts the device pool is grown with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before the
    first jax import — see the multidevice CI job).
    """
    devs = list(devices if devices is not None else jax.devices())
    assert 1 <= n_devices <= len(devs), \
        f"requested {n_devices} mesh devices, have {len(devs)}"
    return Mesh(np.array(devs[:n_devices]), (POD_AXIS,))


def make_hier_mesh(edges: int, pods: int, devices=None) -> Mesh:
    """2-D (edge, pod) mesh over the first ``edges * pods`` devices.

    Device (e, p) is local device ``e * pods + p``, so the flattened
    ("edge", "pod") row order matches the 1-D pod mesh over the same
    pool — which is what makes 2-D vs 1-D row assignments comparable.
    ``edges == 1`` returns the plain 1-D pod mesh: the ``devices=P``
    alias path stays literally the same code (bit-exact by construction).
    ``pods`` must be a power of two — the intra-edge tree reduce is
    log2(P) recursive-doubling rounds.
    """
    assert edges >= 1 and pods >= 1, (edges, pods)
    assert pods & (pods - 1) == 0, \
        f"pod group size {pods} must be a power of two (tree reduce)"
    if edges == 1:
        return make_pod_mesh(pods, devices)
    devs = list(devices if devices is not None else jax.devices())
    need = edges * pods
    assert need <= len(devs), \
        f"requested {edges}x{pods} mesh devices, have {len(devs)}"
    return Mesh(np.array(devs[:need]).reshape(edges, pods),
                (EDGE_AXIS, POD_AXIS))


def is_hier(mesh: Optional[Mesh]) -> bool:
    """True for a 2-D (edge, pod) mesh (E > 1)."""
    return mesh is not None and EDGE_AXIS in mesh.axis_names


def mesh_shape(mesh: Optional[Mesh]) -> Tuple[int, int]:
    """(E, P): edge groups x pod shards per group (1-D mesh -> (1, P))."""
    if mesh is None:
        return (1, 1)
    if is_hier(mesh):
        return (mesh.shape[EDGE_AXIS], mesh.shape[POD_AXIS])
    return (1, mesh.shape[POD_AXIS])


def reduce_axes(mesh: Optional[Mesh]):
    """The mesh axis name(s) a row-wise collective spans — "pod" on the
    1-D mesh, ("edge", "pod") on the hierarchical one.  What the int8dot
    coefficient-scale ``pmax`` (global-K regime pinning) reduces over."""
    return (EDGE_AXIS, POD_AXIS) if is_hier(mesh) else POD_AXIS


def _row_axes(mesh: Mesh):
    """Leading-axis PartitionSpec entry for the K rows: the flattened
    ("edge", "pod") tuple on a 2-D mesh, the bare "pod" name on the 1-D
    one (kept bare so the 1-D specs — and their jit cache keys — are
    byte-identical to the pre-hierarchy ones)."""
    return (EDGE_AXIS, POD_AXIS) if is_hier(mesh) else POD_AXIS


def mesh_size(mesh: Optional[Mesh]) -> int:
    if mesh is None:
        return 1
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n


def row_sharding(mesh: Mesh) -> NamedSharding:
    """(K, D) buffers / (K,) vectors: rows split over the flattened row
    axes — "pod", or ("edge", "pod") on the hierarchical mesh."""
    return NamedSharding(mesh, P(_row_axes(mesh), None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def lead_axis_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """Leading (client/lane) axis on the row axes, trailing dims
    replicated — wave lanes lay over the flattened (edge, pod) axis on
    the hierarchical mesh."""
    return NamedSharding(mesh, P(_row_axes(mesh), *((None,) * (ndim - 1))))


def constrain_rows(tree, mesh: Optional[Mesh]):
    """``with_sharding_constraint`` pinning every leaf's leading axis to the
    mesh row axes (no-op without a mesh).  Used inside the jitted wave
    programs so GSPMD partitions the per-client lanes across devices
    regardless of where the operands were produced."""
    if mesh is None:
        return tree
    return jax.tree_util.tree_map(
        lambda l: jax.lax.with_sharding_constraint(
            l, lead_axis_sharding(mesh, l.ndim)), tree)


def podwise_sums(mesh: Mesh, partial_fn: Callable,
                 quantized: bool | int) -> Callable:
    """The server reduction as a collective: per-shard partials + the
    mesh-shaped fold.

    ``partial_fn(buf_shard, wvec_shard) -> (gsum_local, wsum_local)``
    computes the *unnormalized* weighted row sum of its local shard (the
    staleness discount is elementwise over K, so it is applied per shard).
    The returned callable maps the full ``(buf, wvec)`` — rows sharded
    over the mesh row axes — to the globally reduced ``(gsum (D,),
    wsum ())``, replicated on every device.  Callable from inside a
    jitted program (FlatServer's one-program server round keeps being one
    program).

    1-D pod mesh: ONE global ``psum`` over pod links (the pre-hierarchy
    path, byte-identical specs).  2-D (edge, pod) mesh: the hierarchical
    fold — log2(P) intra-edge ``ppermute`` tree-reduce rounds, then ONE
    cross-edge ``psum`` of the E edge partials
    (:func:`repro.kernels.safl_agg.edge_partial_reduce`); only E operands
    cross the edge boundary instead of E*P.

    ``quantized`` names the buffer payload arity: ``False`` for a single
    (K, D) array, ``True`` for the (q, scales) pair of the q8/q4 wire
    formats, or an int n for an n-tuple payload — 3 for the top-k
    (idx, qv, scales) triple.  Every part is row-sharded the same way,
    and the q8/q4 partial bodies dequantize per shard, so the tree reduce
    always runs over f32 edge partials.
    """
    parts = (2 if quantized else 1) if isinstance(quantized, bool) \
        else int(quantized)
    row_spec = P(_row_axes(mesh), None)
    buf_spec = (row_spec if parts == 1
                else tuple(row_spec for _ in range(parts)))

    if is_hier(mesh):
        from repro.kernels.safl_agg import edge_partial_reduce
        pod_size = mesh.shape[POD_AXIS]

        def local(buf, wvec):
            gsum, wsum = partial_fn(buf, wvec)
            return (edge_partial_reduce(gsum, pod_size=pod_size,
                                        pod_axis=POD_AXIS,
                                        edge_axis=EDGE_AXIS),
                    edge_partial_reduce(jnp.asarray(wsum, jnp.float32),
                                        pod_size=pod_size,
                                        pod_axis=POD_AXIS,
                                        edge_axis=EDGE_AXIS))
    else:
        def local(buf, wvec):
            gsum, wsum = partial_fn(buf, wvec)
            return (jax.lax.psum(gsum, POD_AXIS),
                    jax.lax.psum(jnp.asarray(wsum, jnp.float32), POD_AXIS))

    return shard_map(local, mesh=mesh,
                     in_specs=(buf_spec, P(_row_axes(mesh))),
                     out_specs=(P(), P()), check_vma=False)


def podwise_bank_sums(mesh: Mesh) -> Callable:
    """The streaming server reduction: each shard already holds ITS
    partial sum (one (1, D) row of the AccumBuffer bank, folded on ingest)
    and its slice of the ingest-weight vector, so the per-shard work is
    just reading the row and summing the local weights before the same
    mesh fold :func:`podwise_sums` runs for the buffered channel — on the
    hierarchical mesh that makes each edge group's P bank rows the edge's
    own accumulator (fold-at-edge; finalize = intra-edge tree reduce +
    ONE cross-edge psum).  Maps ``(bank (n_shards, D) rows on the row
    axes, wvec (n_shards*L,) on the row axes)`` to the replicated
    ``(gsum (D,), wsum ())``."""
    return podwise_sums(
        mesh,
        lambda bank_local, w_local: (bank_local.reshape(-1),
                                     jnp.sum(w_local)),
        quantized=False)


def rowwise_fold(mesh: Mesh, fold_fn: Callable) -> Callable:
    """The streaming fold on a mesh: bank row ``ridx`` is folded on the
    shard that owns it, and every other shard keeps its row.

    ``fold_fn(bank, *payload, ridx, w, beta) -> bank`` is the one-device
    fold program body.  Each shard holds one row of the (n_shards, n)
    accumulator bank; the payload and scalars arrive replicated.  The
    fold runs inside ``shard_map`` because Mosaic (Pallas TPU) kernels
    cannot be partitioned automatically: the owner folds its local row
    (local index 0) and the others skip the kernel."""
    row_spec = P(_row_axes(mesh), None)
    pods = mesh.shape[POD_AXIS]

    def local(bank, *rest):
        *payload, ridx, w, beta = rest
        shard = jax.lax.axis_index(POD_AXIS)
        if is_hier(mesh):
            shard = jax.lax.axis_index(EDGE_AXIS) * pods + shard
        return jax.lax.cond(
            shard == ridx,
            lambda b: fold_fn(b, *payload, jnp.int32(0), w, beta),
            lambda b: b, bank)

    def fold(bank, *rest):
        return shard_map(local, mesh=mesh,
                         in_specs=(row_spec,) + (P(),) * len(rest),
                         out_specs=row_spec, check_vma=False)(bank, *rest)

    return fold


def shard_rows(x: jax.Array, mesh: Optional[Mesh]) -> jax.Array:
    """Commit an array's rows to the mesh row axes (no-op without one)."""
    if mesh is None:
        return x
    return jax.device_put(x, row_sharding(mesh))


def edge_traffic(mesh, partial_nbytes: int) -> Dict:
    """Cross-edge traffic model for one server reduction.

    ``mesh`` is a live Mesh / None, or a bare ``(E, P)`` tuple for
    modeling a topology without constructing it (benchmarks on hosts
    with fewer than E*P devices).

    The unit of exchange is a *partial* — one reduced operand of
    ``partial_nbytes`` (the f32 gsum a shard contributes, plus its scalar
    weight mass).  A flat global psum over N = E*P shards has no
    locality: all N partials participate in the global exchange, so every
    edge's P partials cross the (slow) edge boundary.  The hierarchical
    fold crosses with exactly ONE partial per edge — the tree-reduced
    edge partial — so measured cross-edge bytes shrink by N/E = P.

    Returns a dict with the measured-per-aggregation byte counts:
    ``cross_edge_bytes`` (this mesh), ``flat_cross_bytes`` (the 1-D
    global-psum equivalent over the same N shards) and
    ``cross_edge_reduction`` = flat/hier = P.  On a 1-D (or absent) mesh
    the two coincide and the reduction factor is 1.0.
    """
    if isinstance(mesh, tuple):
        edges, pods = mesh
        hier = edges > 1
    else:
        edges, pods = mesh_shape(mesh)
        hier = is_hier(mesh)
    n = edges * pods
    per_partial = int(partial_nbytes) + 4  # + the f32 weight-mass scalar
    flat = n * per_partial
    # only a hierarchical mesh has an edge boundary to save across; the
    # 1-D global psum IS the flat baseline (all N partials cross)
    cross = edges * per_partial if hier else flat
    return {
        "mesh_shape": (edges, pods),
        "cross_edge_partials": edges,
        "cross_edge_bytes": cross,
        "flat_cross_bytes": flat,
        "cross_edge_reduction": (flat / cross) if cross else 1.0,
    }
