"""Production mesh construction (TPU v5e pods; DESIGN.md §5).

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis carries the paper's federated aggregation collective.

Functions only (no module-level jax device state) so imports stay pure; the
dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512 before any
jax import (see dryrun.py).
"""
from __future__ import annotations

import jax

# v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link


def make_auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto (GSPMD) mode.  The
    sharding rules (repro.sharding.rules) place arrays through
    ``NamedSharding`` and let the partitioner propagate the rest;
    ``jax.make_mesh`` defaults to Explicit axes, under which an input's
    sharding becomes part of its type and a sharded-index gather (the
    embedding lookup) is refused."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_debug_mesh(n_devices: int = 1):
    """CPU-sized mesh for tests: (1, n) over ("data", "model")."""
    return make_auto_mesh((1, n_devices), ("data", "model"))


def make_pod_mesh(n_devices: int):
    """1-D mesh over the "pod" axis — the paper's federated aggregation
    axis, used by the multi-device SAFL engine (FLConfig.devices > 1) to
    shard the flat (K, D) upload channel and the vmapped waves row-wise
    (repro.sharding.flat).  On CPU hosts grow the device pool with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the
    first jax import."""
    from repro.sharding.flat import make_pod_mesh as _mk
    return _mk(n_devices)


def make_hier_mesh(edges: int, pods: int):
    """2-D (edge, pod) mesh — the hierarchical SAFL aggregation topology
    (FLConfig.mesh_shape=(E, P)): per-shard partials tree-reduce within
    their edge group over the pod sub-axis, then ONE cross-edge psum of
    the E edge partials reaches the server step (repro.sharding.flat).
    edges == 1 builds the plain 1-D pod mesh (the ``devices=P`` alias)."""
    from repro.sharding.flat import make_hier_mesh as _mk
    return _mk(edges, pods)


def cross_edge_time_s(cross_edge_bytes: int,
                      link_bw: float = ICI_BW) -> float:
    """Roofline seconds for one aggregation's cross-edge traffic over one
    slow inter-edge link (default: one v5e ICI link — real edge uplinks
    are slower still, which only widens the hierarchy's win).  Pairs with
    ``FlatServer.traffic["cross_edge_bytes"]`` to turn the measured ~P x
    byte reduction into projected wall-clock on hardware where the
    cross-edge hop dominates."""
    return float(cross_edge_bytes) / float(link_bw)


def mesh_chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
