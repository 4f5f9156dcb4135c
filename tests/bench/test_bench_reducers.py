"""The per-layer readers (``bench/layers/``) on traces with known
answers: a hand-built one whose every number is worked out here, and a
small recorded chip trace (``fixtures/``)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tinycell import BENCH, harness  # noqa: E402

import devtrace  # noqa: E402
import layers_common  # noqa: E402

MS = 1_000_000  # ns


def reader(name):
    return harness.load_module(os.path.join(BENCH, "layers", name + ".py"),
                               "t_reader_" + name).read


def built_trace():
    """Two rounds in a 120 ms window on one device.  Round 1: wave 0-40,
    two folds 40-42 and 43-45, finalize 46-47, eval 48-58 ms; round 2 the
    same 60 ms later.  Ops cover each program except 1 ms of the wave."""
    mods, ops = [], []
    for r in (0, 60):
        progs = [("jit_round_fn", 0, 40), ("jit__fold", 40, 42),
                 ("jit__fold", 43, 45), ("jit__finalize", 46, 47),
                 ("jit__lambda_", 48, 58)]
        for i, (n, s, e) in enumerate(progs):
            mods.append([f"{n}({i})", (r + s) * MS, (r + e) * MS])
        ops += [["%fusion.1 fusion", r * MS, (r + 39) * MS],
                ["%_fold.1 custom-call", (r + 40) * MS, (r + 42) * MS],
                ["%_fold.1 custom-call", (r + 43) * MS, (r + 45) * MS],
                ["%fusion.2 fusion", (r + 46) * MS, (r + 47) * MS],
                ["%convolution.3 convolution", (r + 48) * MS, (r + 58) * MS]]
    return {"window": [0, 120 * MS], "rounds": 2,
            "host": [["bench.window", 0, 120 * MS],
                     ["bench.round", 0, 59 * MS],
                     ["bench.round", 60 * MS, 119 * MS]],
            "devices": [{"name": "/device:TPU:0",
                         "lines": {"XLA Modules": mods, "XLA Ops": ops}}]}


def ctx_for(tr, chips=1):
    cell = harness.find_cell("resnet18.as-f32")
    return layers_common.context(dict(cell, chips=chips), tr,
                                 {"bf16_flops": 1.97e14,
                                  "hbm_bytes_per_s": 8.19e11})


def test_built_trace():
    tr = built_trace()
    ctx = ctx_for(tr)
    busy_ms = 2 * (39 + 2 + 2 + 1 + 10)
    assert ctx["busy_s"] == pytest.approx(busy_ms / 1e3)
    assert ctx["window_s"] == pytest.approx(0.12)
    assert reader("device_idle_frac")(tr, ctx) == pytest.approx(
        1 - busy_ms / 120)
    assert reader("launches_per_round")(tr, ctx) == 5
    assert reader("wave_ms_per_round")(tr, ctx) == pytest.approx(40)
    assert reader("server_ms_per_round")(tr, ctx) == pytest.approx(5)
    # 2 rounds x 8 uploads x 12 D bytes, over 8 ms of fold kernel
    need = 16 * 12 * 11_173_962 / 8.19e11
    assert reader("fold_roofline")(tr, ctx) == pytest.approx(
        100 * need / 8e-3)
    flops = 8 * 128 * 3 * 963_718_656 + 1024 * 963_718_656
    assert reader("round_mfu")(tr, ctx) == pytest.approx(
        100 * 2 * flops / (0.12 * 1.97e14))
    bd = layers_common.breakdown(tr, ctx)
    assert bd["device_ops"][0] == ["%fusion.1 fusion", pytest.approx(0.078)]
    gaps = {n: v for n, v in bd["idle_gaps"]}
    assert gaps == pytest.approx({
        "in round: inside jit_round_fn": 0.002,
        "in round: jit__fold -> jit__fold": 0.002,
        "in round: jit__fold -> jit__finalize": 0.002,
        "in round: jit__finalize -> jit__lambda_": 0.002,
        # 58-60 ms: the host closes round 1 (until 59) and opens round 2
        "between rounds: jit__lambda_ -> jit_round_fn": 0.002,
        "between rounds: jit__lambda_ -> end": 0.002})


def test_union_of_overlapping_intervals():
    assert layers_common.union_ns([(0, 10), (5, 12), (20, 21)]) == 13
    assert layers_common.union_ns([]) == 0


def test_readers_find_nothing_in_an_empty_trace():
    tr = {"window": [0, MS], "rounds": 0, "host": [], "devices": []}
    ctx = ctx_for(tr)
    for m in harness.benchmark()["per_layer"]:
        assert reader(m["name"])(tr, ctx) is None, m["name"]


FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "resnet18.as-f32.2rounds.json.gz")


def test_recorded_chip_trace():
    """Two rounds of ``resnet18.as-f32`` traced on one TPU v5e (0.708 s):
    the readers against sums worked out here from the raw events."""
    import numpy as np

    tr = devtrace.from_json(FIXTURE)
    ctx = ctx_for(tr)
    assert tr["rounds"] == 2
    lo, hi = tr["window"]
    lines = tr["devices"][0]["lines"]
    # busy time by a 1 us occupancy grid (a second way to take the union)
    grid = np.zeros((hi - lo) // 1000 + 1, bool)
    for _n, s, e in lines["XLA Ops"]:
        grid[(s - lo) // 1000:(e - lo) // 1000] = True
    assert ctx["busy_s"] == pytest.approx(grid.sum() / 1e6, rel=1e-3)
    assert ctx["busy_s"] == pytest.approx(0.264173953)
    assert reader("device_idle_frac")(tr, ctx) == pytest.approx(
        1 - 0.264173953 / 0.707940281)
    mods = lines["XLA Modules"]
    assert reader("launches_per_round")(tr, ctx) == len(mods) / 2 == 505
    wave = sum(e - s for n, s, e in mods if n.startswith("jit_round_fn("))
    assert reader("wave_ms_per_round")(tr, ctx) == pytest.approx(
        wave / 2e6) == pytest.approx(96.094212)
    server = sum(e - s for n, s, e in mods
                 if n.startswith(("jit__fold(", "jit__finalize(")))
    assert reader("server_ms_per_round")(tr, ctx) == pytest.approx(
        server / 2e6) == pytest.approx(13.071103)
    folds = [e - s for n, s, e in lines["XLA Ops"]
             if n.startswith("%_fold") and n.endswith(" custom-call")]
    assert len(folds) == 16  # 8 uploads a round
    need = 16 * 12 * 11_173_962 / 8.19e11
    assert reader("fold_roofline")(tr, ctx) == pytest.approx(
        100 * need / (sum(folds) / 1e9)) == pytest.approx(21.534126)
    assert 0 < reader("round_mfu")(tr, ctx) < 100
