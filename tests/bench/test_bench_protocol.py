"""The configuration protocol (``bench/harness.py``'s docstring): each
configuration's module makes its data, weights and work.

* The CIFAR configurations give the same data, weights, round FLOPs and
  row length as before the protocol moved them into the configuration
  modules: digests and numbers recorded at a tiny size from the harness
  that made CIFAR data itself.
* A tiny token configuration (``fixtures/tiny-lm``: next-token targets,
  a frozen embedding and head, a trained adapter) runs through
  ``harness.make_data``, ``reference.run`` and ``check.readings`` with
  nothing of the benchmark edited.
* ``build_engine`` hands the engine a frozen part only where ``init``
  returns one.
"""
import copy
import hashlib
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tinycell  # noqa: E402
from tinycell import harness  # noqa: E402

import check  # noqa: E402
import layers_common  # noqa: E402
import reference  # noqa: E402
import work  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
SEED = 2**33 + 5


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


#: recorded from the harness before the protocol (``tiny_cell`` sizes for
#: data and weights, seed 2**33 + 5; the full cells for the FLOPs)
BEFORE = {
    "resnet18.as-f32": dict(data="65899824b9969cac",
                            weights="35f5461f2436276f",
                            round_flops=3947391614976.0,
                            tiny_round_flops=73758720.0),
    "vgg16.aa-f32": dict(data="8d7ea9684170e380",
                         weights="deb0b9a99cc5ee34",
                         round_flops=2030292697088.0,
                         tiny_round_flops=222063263744.0),
}


@pytest.mark.parametrize("name", list(BEFORE))
def test_cifar_inputs_and_work_as_before(name):
    import jax

    want = BEFORE[name]
    cell = tinycell.tiny_cell(name)
    d = harness.make_data(cell["cfg"], cell["traffic"], SEED)
    assert digest(d["xs"], d["ys"], d["test_x"], d["test_y"]) == \
        want["data"]
    w = harness.make_weights(cell, SEED)
    assert len(w) == 2
    assert digest(*jax.tree_util.tree_leaves(jax.device_get(w))) == \
        want["weights"]
    assert work.round_flops(cell["cfg"], cell["ref"], cell["traffic"]) \
        == want["tiny_round_flops"]
    full = harness.find_cell(name)
    assert work.round_flops(full["cfg"], full["ref"], full["traffic"]) \
        == want["round_flops"]
    assert work.row_length(full["cfg"], full["ref"]) == \
        full["cfg"]["n_params"]
    assert full["ref"].flops_train(full["cfg"]) == \
        3 * full["ref"].flops_forward(full["cfg"])


@pytest.mark.parametrize("name", list(BEFORE))
def test_fold_bytes_from_the_row(name):
    cell = harness.find_cell(name)
    tr = {"window": [0, 1], "rounds": 1, "host": [], "devices": []}
    ctx = layers_common.context(cell, tr, tinycell.peak())
    assert ctx["fold_bytes"] == 12 * cell["cfg"]["n_params"]


# ---------------------------------------------------------------------------
# a token configuration with a frozen part
# ---------------------------------------------------------------------------


def token_cell(aggregation: str = "fedsgd") -> dict:
    """The fixture configuration under the tiny cell's traffic."""
    cfg = harness.read_json(os.path.join(FIXTURES, "tiny-lm.json"))
    tr = copy.deepcopy(tinycell.tiny_cell()["traffic"])
    tr["engine"].update(aggregation=aggregation, client_lr=0.5,
                        server_lr=1.0, local_batch_size=16)
    return dict(name="tiny-lm", cfg=cfg, ref=harness.config_module(cfg),
                traffic=tr, limits=tinycell.TINY_LIMITS, chips=1)


def next_token_ce(cfg, params, frozen, x, y) -> float:
    """Mean over every position of -log softmax(logits)[next token], in
    float64, with the targets taken from the sequences themselves."""
    e = np.asarray(frozen["embed"], np.float64)
    head = np.asarray(frozen["head"], np.float64)
    a = np.asarray(params["a"], np.float64)
    b = np.asarray(params["b"], np.float64)
    seq = np.concatenate([x, y[:, -1:]], axis=1)
    h = e[seq[:, :-1]]
    logits = (h + h @ a @ b) @ head
    logz = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) \
        + logits.max(-1)
    tgt = np.take_along_axis(logits, seq[:, 1:, None], -1)[..., 0]
    return float(np.mean(logz - tgt))


def test_token_data_has_shifted_targets():
    cell = token_cell()
    d = harness.make_data(cell["cfg"], cell["traffic"], SEED)
    pop = cell["traffic"]["population"]
    t = cell["cfg"]["seq_len"]
    assert d["xs"].shape == (pop["n_clients"], 2, 16, t)
    assert d["ys"].shape == d["xs"].shape
    assert d["xs"].dtype == np.int32 and d["ys"].dtype == np.int32
    np.testing.assert_array_equal(d["ys"][..., :-1], d["xs"][..., 1:])
    np.testing.assert_array_equal(d["test_y"][:, :-1], d["test_x"][:, 1:])
    assert d["xs"].max() > 256  # ids bfloat16 would round
    d2 = harness.make_data(cell["cfg"], cell["traffic"], SEED)
    assert digest(*d.values()) == digest(*d2.values())


@pytest.mark.parametrize("aggregation", ["fedsgd", "fedavg"])
def test_token_configuration_through_the_reference(aggregation):
    import jax

    cell = token_cell(aggregation)
    cfg, tr = cell["cfg"], cell["traffic"]
    data = harness.make_data(cfg, tr, SEED)
    pop = harness.population(tr, SEED)
    weights = harness.make_weights(cell, SEED)
    assert len(weights) == 3
    frozen = jax.device_get(weights[2])
    before = digest(*jax.tree_util.tree_leaves(frozen))
    n = int(tr["checked_rounds"])
    ref = reference.run(cfg, cell["ref"], tr, pop, data, weights, n)
    # the frozen part is never trained, so it is the same after the run
    assert digest(*jax.tree_util.tree_leaves(
        jax.device_get(weights[2]))) == before
    assert digest(*jax.tree_util.tree_leaves(jax.device_get(
        harness.make_weights(cell, SEED)[2]))) == before
    # only the trained part is recorded, and it moves
    for p in ref["params"]:
        assert set(p) == {"a", "b"}
    assert not np.array_equal(ref["params"][0]["a"], ref["params"][-1]["a"])
    # every evaluation loss is the next-token cross-entropy of the global
    # adapter over the frozen embedding and head
    for i, loss in enumerate(ref["losses"]):
        want = next_token_ce(cfg, ref["params"][i + 1], frozen,
                             data["test_x"], data["test_y"])
        assert loss == pytest.approx(want, rel=1e-5)
    assert ref["adopted"] >= 1
    read = check.readings(ref, ref)
    assert "residual" not in read  # the f32 wire keeps no residuals
    assert all(read[k] == 0 for k in check.NUMBERS if k in read), read
    assert read["left_out"] == 0
    ok, _rows = check.verdict(read, tinycell.TINY_LIMITS)
    assert ok


def test_bf16_control_keeps_token_ids(monkeypatch):
    import jax.numpy as jnp

    cell = token_cell()
    cfg, tr = cell["cfg"], cell["traffic"]
    data = harness.make_data(cfg, tr, SEED)
    pop = harness.population(tr, SEED)
    seen = []
    mod = cell["ref"]
    orig = mod.apply

    def apply(cfg, params, state, x, train, frozen=None):
        seen.append((x.dtype, params["a"].dtype, frozen["embed"].dtype))
        return orig(cfg, params, state, x, train, frozen=frozen)

    monkeypatch.setattr(mod, "apply", apply)
    monkeypatch.setattr(reference, "_PROGRAMS", {})
    ref = reference.run(cfg, mod, tr, pop, data,
                        harness.make_weights(cell, SEED), 2)
    ctl = reference.run(cfg, mod, tr, pop, data,
                        harness.make_weights(cell, SEED), 2,
                        dtype="bfloat16", precision="default")
    assert (jnp.int32, jnp.float32, jnp.float32) in seen
    assert (jnp.int32, jnp.bfloat16, jnp.bfloat16) in seen
    assert all(x == jnp.int32 for x, _p, _f in seen)
    read = check.readings(ctl, ref)
    assert np.isfinite(read["loss"]) and read["grad"] > 0


# ---------------------------------------------------------------------------
# the engine gets a frozen part only where there is one
# ---------------------------------------------------------------------------


def test_build_engine_passes_frozen_only_when_init_returns_it(monkeypatch):
    harness.use_program()
    import repro.core

    calls = []

    class StandIn(repro.core.FLEngine):
        def __init__(self, *args, **kwargs):
            calls.append(dict(kwargs))
            kwargs.pop("frozen", None)
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(repro.core, "FLEngine", StandIn)
    cell = tinycell.tiny_cell()
    data = harness.make_data(cell["cfg"], cell["traffic"], SEED)
    params, state = harness.make_weights(cell, SEED)
    harness.build_engine(cell, SEED, data, (params, state))
    assert calls[-1] == {}
    frozen = {"table": np.zeros((3,), np.float32)}
    harness.build_engine(cell, SEED, data, (params, state, frozen))
    assert set(calls[-1]) == {"frozen"} and calls[-1]["frozen"] is frozen
