"""The Moonlight configuration of the benchmark on the CPU: its module at
tiny widths (``fixtures/tiny-moonlight.json``) through ``harness`` and
the engine, against ``reference.run`` by ``check.py``; its work from
shapes at the published widths; and the readers of the layer it adds
(``experts``) on a built trace."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tinycell  # noqa: E402
from tinycell import BENCH, harness  # noqa: E402

import check  # noqa: E402
import layers_common  # noqa: E402
import reference  # noqa: E402
import work  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
CELL = "moonlight.aa-lora-s1024"
SEED = 2**33 + 7
MS = 1_000_000


def tiny_moonlight() -> dict:
    """The cell's traffic shrunk, over the fixture's tiny widths."""
    cell = harness.find_cell(CELL)
    cfg = harness.read_json(os.path.join(FIXTURES, "tiny-moonlight.json"))
    tr = dict(cell["traffic"])
    tr["population"] = dict(tr["population"], n_clients=8,
                            samples_per_client=4)
    tr["engine"] = dict(tr["engine"], k=4, client_lr=0.05)
    tr.update(eval_samples=8, checked_rounds=4)
    return dict(cell, cfg=cfg, ref=harness.config_module(cfg), traffic=tr,
                limits=tinycell.TINY_LIMITS)


def test_tiny_moonlight_through_the_harness_and_check():
    import jax

    cell = tiny_moonlight()
    cfg, tr = cell["cfg"], cell["traffic"]
    data = harness.make_data(cfg, tr, SEED)
    assert data["xs"].shape == (8, 2, 2, cfg["seq_len"])
    weights = harness.make_weights(cell, SEED)
    assert len(weights) == 3 and weights[1] == {}
    eng = harness.build_engine(cell, SEED, data, weights)
    assert eng.frozen is not None
    prog = harness.checked_rounds(eng, 4, jax.device_get(weights[0]))
    ref = reference.run(cfg, cell["ref"], tr, harness.population(tr, SEED),
                        data, harness.make_weights(cell, SEED), 4)
    read = check.readings(prog, ref)
    assert read["left_out"] == 0 and read["adopted"] >= 1
    ok, rows = check.verdict(read, tinycell.TINY_LIMITS)
    assert ok, rows
    # the engine recorded the held experts' load of every evaluation
    held = [r.reported["routed_held"] for r in eng.metrics.records]
    assert len(held) == 4 and all(0 < h <= 6 for h in held)


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(BENCH, "configs",
                            "moonlight-16b-a3b-ep8.py")).read()
    assert "repro" not in src.replace("reproduc", "")


def test_work_at_the_published_widths():
    """The uploaded row is the adapters (1,315,840 values); the base is
    about 568 M values; a token's forward pass about 0.58 GFLOP and its
    training step a little over twice that; the held experts' products
    at the mean load of 6 x 8/64 assignments a token and layer."""
    import jax
    import numpy as np

    cell = harness.find_cell(CELL)
    cfg, ref, tr = cell["cfg"], cell["ref"], cell["traffic"]
    assert work.row_length(cfg, ref) == 1_315_840
    base = jax.eval_shape(lambda k: ref.init(cfg, k),
                          jax.random.PRNGKey(0))[2]
    n_base = sum(int(np.prod(a.shape))
                 for a in jax.tree_util.tree_leaves(base))
    assert 560e6 < n_base < 575e6
    per_token = ref.flops_forward(cfg) / cfg["seq_len"]
    assert 0.55e9 < per_token < 0.62e9
    assert 2.0 < ref.flops_train(cfg) / ref.flops_forward(cfg) < 2.2
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    per_assign = 3 * 2 * d * f
    train_tokens = 8 * 8 * 1024
    eval_tokens = 32 * 1024
    want = 4 * 0.75 * per_assign * (2 * train_tokens + eval_tokens)
    assert ref.expert_flops_round(cfg, tr) == pytest.approx(want)
    assert work.round_flops(cfg, ref, tr) == pytest.approx(
        ref.flops_train(cfg) * 64 + ref.flops_forward(cfg) * 32)


def reader(name):
    return harness.load_module(os.path.join(BENCH, "layers", name + ".py"),
                               "t_reader_" + name).read


def _trace(ops, rounds=2):
    return {"window": [0, 100 * MS], "rounds": rounds,
            "host": [["bench.window", 0, 100 * MS]],
            "devices": [{"name": "/device:TPU:0",
                         "lines": {"XLA Ops": ops}}]}


def _ctx(cell_name, tr):
    cell = harness.find_cell(cell_name)
    return layers_common.context(cell, tr, {"bf16_flops": 1.97e14,
                                            "hbm_bytes_per_s": 8.19e11})


def test_expert_readers_on_a_built_trace():
    """Two rounds: grouped products of 30 ms and 10 ms, their metadata op
    and a fusion, which do not count."""
    ops = [["%ragged-dot-none custom-call", 0, 30 * MS],
           ["%ragged-dot-none.7 custom-call", 40 * MS, 50 * MS],
           ["%ragged-dot-metadata custom-call", 50 * MS, 60 * MS],
           ["%fusion.3 fusion", 60 * MS, 90 * MS]]
    tr = _trace(ops)
    ctx = _ctx(CELL, tr)
    assert reader("expert_ms_per_round")(tr, ctx) == pytest.approx(20.0)
    cell = harness.find_cell(CELL)
    flops = cell["ref"].expert_flops_round(cell["cfg"], cell["traffic"])
    assert reader("expert_roofline")(tr, ctx) == pytest.approx(
        100 * 2 * flops / (0.040 * 1.97e14))
    # another cell's context: the roofline finds no configuration of its
    # own there, and the time alone reads as it is
    other = _ctx("resnet18.as-f32", tr)
    assert reader("expert_roofline")(tr, other) is None
    assert reader("expert_ms_per_round")(_trace(ops[2:]), ctx) is None
