"""Per-client rows and model states in the batched semi-async path: one
program per stack or slice of a whole ``(row, state)`` tree
(``core/client.py`` ``stack_states`` / ``take_states`` /
``split_states``), never one eager op per leaf.

  * the helpers equal the eager per-leaf ``tree_map`` code bit for bit,
    on a ResNet-18 BatchNorm state tree (40 leaves), alone and beside
    a lane's row;
  * row indices are a traced argument: other rows compile nothing;
  * an empty tree (a model without BatchNorm) comes back with nothing
    dispatched;
  * a stateful engine run with the helpers equals the same run with the
    eager per-leaf code put back, on every gather path (wave-0 stack,
    all-adopt broadcast, all-carry gather, mixed), and on a two-device
    mesh where more than one device is there.
"""
import collections
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import FLConfig
from repro.core import FLEngine, client
from repro.core import safl as saflmod
from repro.core.client import (broadcast_states, split_states,
                               stack_states, take_states)
from repro.data import build_client_shards, make_dataset, train_test_split
from repro.models.vision_cnn import build_paper_model

#: the host event of one program execution on jaxlib's CPU client
EXECUTE = "PjRtCpuExecutable::Execute"

multidevice = pytest.mark.skipif(
    jax.device_count() < 2, reason="needs >1 jax device (set XLA_FLAGS="
    "--xla_force_host_platform_device_count before importing jax)")


def eager_stack_states(trees):
    """The per-leaf reference: n + 1 eager dispatches per leaf."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def eager_broadcast_states(tree, n):
    """The per-leaf reference: an eager broadcast per leaf."""
    return jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l, (n,) + l.shape), tree)


def eager_take_states(tree, idx):
    """The per-leaf reference: an eager index per leaf."""
    if isinstance(idx, (list, tuple)):
        idx = jnp.asarray(idx)
    return jax.tree_util.tree_map(lambda l: l[idx], tree)


def eager_split_states(tree, rows):
    """The per-leaf reference: an eager index per leaf and row."""
    return [eager_take_states(tree, row) for row in rows]


def assert_trees_equal(a, b):
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b), strict=True):
        assert la.dtype == lb.dtype and la.shape == lb.shape
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def executions(fn, tmp_path):
    """Programs executed by ``fn()`` on the CPU client, from a profile."""
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(fn())
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    return sum(e.name == EXECUTE
               for plane in ProfileData.from_file(path).planes
               for line in plane.lines for e in line.events)


@pytest.fixture(scope="module")
def resnet():
    """ResNet-18 at width 4: ``(params, state, apply_fn)``."""
    return build_paper_model("resnet18", jax.random.PRNGKey(0), width=4)


@pytest.fixture(scope="module")
def lane_states(resnet):
    """Eight distinct ResNet-18 state trees."""
    leaves, treedef = jax.tree_util.tree_flatten(resnet[1])
    assert len(leaves) == 40  # 20 BatchNorms x (mean, var)
    rng = np.random.default_rng(1)
    return [jax.tree_util.tree_unflatten(treedef, [
        jnp.asarray(rng.standard_normal(l.shape), l.dtype) for l in leaves])
        for _ in range(8)]


def _rows(n):
    """n distinct (5,) rows, standing in for the clients' flat params."""
    return [jnp.arange(5, dtype=jnp.float32) + 5 * i for i in range(n)]


@pytest.mark.parametrize("with_rows", [False, True], ids=["states", "pairs"])
@pytest.mark.parametrize("n", [1, 3, 8])
def test_stack_states_equals_eager_stack(lane_states, n, with_rows):
    # pairs: a lane's row and state together, as the gather stacks them
    lanes = (list(zip(_rows(n), lane_states[:n])) if with_rows
             else lane_states[:n])
    assert_trees_equal(stack_states(lanes), eager_stack_states(lanes))


@pytest.mark.parametrize("n", [1, 8])
def test_broadcast_states_equals_eager_broadcast(lane_states, n):
    # the global row and state, as the all-adopt gather broadcasts them
    pair = (_rows(1)[0], lane_states[0])
    assert_trees_equal(broadcast_states(pair, n),
                       eager_broadcast_states(pair, n))


@pytest.mark.parametrize("idx", [[6], [3, 0, 7, 3], np.arange(5)],
                         ids=["one", "vector", "prefix"])
def test_take_states_equals_eager_index(lane_states, idx):
    stacked = eager_stack_states(lane_states)
    assert_trees_equal(take_states(stacked, idx),
                       eager_take_states(stacked, idx))
    if isinstance(idx, np.ndarray):  # the padded wave's real-lane prefix
        assert_trees_equal(take_states(stacked, idx), jax.tree_util.tree_map(
            lambda l: l[:len(idx)], stacked))


@pytest.mark.parametrize("rows", [[6], [0, 7, 3, 0]], ids=["one", "four"])
def test_split_states_equals_eager_rows(lane_states, rows):
    stacked = eager_stack_states(lane_states)
    # a lane's row and state together, as the refresh slices them
    pair = (jnp.stack(_rows(8)), stacked)
    assert_trees_equal(split_states(pair, rows),
                       eager_split_states(pair, rows))


@pytest.mark.parametrize("helper, first, second", [
    (take_states, [1, 2], [4, 0]), (split_states, [1], [4])],
    ids=["take", "split"])
def test_new_rows_compile_nothing(lane_states, helper, first, second):
    # six lanes: a shape no other test slices, so the first call compiles
    stacked = stack_states(lane_states[:6])
    jitted = {take_states: client._take_states,
              split_states: client._split_states}[helper]
    eager = {take_states: eager_take_states,
             split_states: eager_split_states}[helper]
    before = jitted._cache_size()
    out = helper(stacked, first)
    assert jitted._cache_size() == before + 1
    out2 = helper(stacked, second)
    assert jitted._cache_size() == before + 1
    assert_trees_equal(out, eager(stacked, first))
    assert_trees_equal(out2, eager(stacked, second))


def test_one_program_per_call_and_none_for_an_empty_tree(lane_states,
                                                         tmp_path):
    stacked = stack_states(lane_states)
    take_states(stacked, [2])
    split_states(stacked, [2, 5])
    broadcast_states(lane_states[0], 8)
    # the stateful calls show the event is there to count: one program
    # for the whole 40-leaf tree
    assert executions(lambda: stack_states(lane_states),
                      tmp_path / "stack") == 1
    assert executions(lambda: take_states(stacked, [2]),
                      tmp_path / "take") == 1
    assert executions(lambda: split_states(stacked, [2, 5]),
                      tmp_path / "split") == 1
    assert executions(lambda: broadcast_states(lane_states[0], 8),
                      tmp_path / "broadcast") == 1
    assert stack_states([{}] * 3) == {}
    assert take_states({}, [2]) == {}
    assert split_states({}, [2, 5]) == [{}, {}]
    assert executions(lambda: (stack_states([{}] * 3), take_states({}, [2]),
                               split_states({}, [2, 5])),
                      tmp_path / "empty") == 0


# ----------------- engine: helpers vs the eager per-leaf code -----------


@pytest.fixture(scope="module")
def resnet_setup(resnet):
    """The sizes of test_quantized_channel's resnet_setup."""
    ds = make_dataset("cifar10", n=240, seed=0, hw=16)
    tr, te = train_test_split(ds)
    return (tr, te) + tuple(resnet)


#: one wave of K a horizon, as in the benchmark's traffic
PLAIN = dict(n_clients=6, k=3, rounds=2)
#: fast clients upload several times a horizon and crashes reset clients
#: between their lanes: waves > 0 take the broadcast, carry and mixed paths
CHURN = dict(n_clients=4, k=4, rounds=8, speed_sigma=2.0, fault_crash_p=0.5)
#: the churn schedule with the wave lanes and rows over two devices
MESH = dict(CHURN, devices=2)


def _run(setup, aggregation, wire, n_clients, rounds, **kw):
    tr, te, p0, s0, apply_fn = setup
    shards = build_client_shards(tr, "iid", n_clients=n_clients,
                                 batch_size=8)
    cfg = FLConfig(n_clients=n_clients, mode="semi_async",
                   aggregation=aggregation, client_lr=0.05,
                   server_lr=0.05 if aggregation == "fedsgd" else 1.0,
                   target_accuracy=0.9, wire=wire, batch_clients=True, **kw)
    eng = FLEngine(cfg, apply_fn, "image", p0, s0, shards,
                   te.x[:32], te.y[:32])
    return eng.run(rounds), eng


def _gather_paths(monkeypatch):
    """Counts the gather path each wave takes."""
    paths = collections.Counter()
    gather = FLEngine._gather_wave

    def spy(self, w, cids, force_global, carry, *args):
        rows = [None if (cid, w) in force_global else carry.get(cid)
                for cid in cids]
        paths["stack" if w == 0
              else "broadcast" if all(rv is None for rv in rows)
              else "carry" if all(rv is not None for rv in rows)
              else "mixed"] += 1
        return gather(self, w, cids, force_global, carry, *args)

    monkeypatch.setattr(FLEngine, "_gather_wave", spy)
    return paths


@pytest.mark.parametrize("schedule, aggregation, wire", [
    (PLAIN, "fedsgd", "f32"), (PLAIN, "fedsgd", "q8"),
    (PLAIN, "fedavg", "f32"), (PLAIN, "fedavg", "q8"),
    (CHURN, "fedsgd", "f32"), (CHURN, "fedavg", "q8"),
    pytest.param(MESH, "fedsgd", "f32", marks=multidevice),
    pytest.param(MESH, "fedavg", "q8", marks=multidevice)],
    ids=["plain-fedsgd-f32", "plain-fedsgd-q8", "plain-fedavg-f32",
         "plain-fedavg-q8", "churn-fedsgd-f32", "churn-fedavg-q8",
         "mesh2-fedsgd-f32", "mesh2-fedavg-q8"])
def test_engine_states_bitwise_equal_to_eager_per_leaf(
        resnet_setup, monkeypatch, schedule, aggregation, wire):
    paths = _gather_paths(monkeypatch)
    res, eng = _run(resnet_setup, aggregation, wire, **schedule)
    if schedule is not PLAIN:
        assert {"stack", "broadcast", "carry", "mixed"} <= set(paths), paths
    monkeypatch.setattr(saflmod, "stack_states", eager_stack_states)
    monkeypatch.setattr(saflmod, "broadcast_states", eager_broadcast_states)
    monkeypatch.setattr(saflmod, "take_states", eager_take_states)
    monkeypatch.setattr(saflmod, "split_states", eager_split_states)
    ref_res, ref = _run(resnet_setup, aggregation, wire, **schedule)
    np.testing.assert_array_equal(np.asarray(eng._flat_params),
                                  np.asarray(ref._flat_params))
    assert_trees_equal(eng.global_state, ref.global_state)
    for c, rc in zip(eng.clients, ref.clients, strict=True):
        assert_trees_equal(c.model_state, rc.model_state)
    for row, ref_row in zip(eng._client_flats, ref._client_flats,
                            strict=True):
        np.testing.assert_array_equal(np.asarray(row), np.asarray(ref_row))
    assert res.metrics.records == ref_res.metrics.records
    assert len(res.metrics.records) == schedule["rounds"]
