"""The benchmark harness on the CPU: every cell's files are found by
name, the command refuses to run off the chip, and a run at a tiny size
prints the last line the contract asks for."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tinycell  # noqa: E402
from tinycell import BENCH, harness  # noqa: E402

import check  # noqa: E402
import run  # noqa: E402

ROOT = os.path.dirname(BENCH)
BENCHMARK = harness.benchmark()
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
END_TO_END = {m["name"] for m in BENCHMARK["end_to_end"]}


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_found_by_name(name):
    cell = harness.find_cell(name)
    cfg_entry = {c["name"]: c for c in BENCHMARK["configs"]}[
        cell["entry"]["config"]]
    assert cfg_entry["file"] == f"bench/configs/{cfg_entry['name']}.json"
    assert cell["cfg"]["name"] == cfg_entry["name"]
    assert set(cfg_entry["reduced"]) <= set(cell["cfg"]["published"])
    for fn in ("make_data", "init", "apply", "flops_forward",
               "flops_train"):
        assert callable(getattr(cell["ref"], fn))
    assert cell["limits"] is not None, "no limits file for " + name
    # ``residual`` is read only where the wire keeps error-feedback
    # residuals
    eng = cell["traffic"]["engine"]
    keeps = eng.get("error_feedback") and eng.get("wire", "f32") != "f32"
    assert set(cell["limits"]["limits"]) == set(check.NUMBERS) - (
        set() if keeps else {"residual"})
    for key in ("engine", "population", "eval_samples", "checked_rounds"):
        assert key in cell["traffic"]


def test_every_per_layer_metric_has_a_reader():
    for m in BENCHMARK["per_layer"]:
        path = os.path.join(BENCH, "layers", m["name"] + ".py")
        mod = harness.load_module(path, "t_layer_" + m["name"])
        assert callable(mod.read), m["name"]
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def _bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_off_the_chip():
    p = _bench(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


@pytest.mark.parametrize("var", ["REPRO_AGG_BACKEND",
                                 "REPRO_PALLAS_INTERPRET"])
def test_refuses_a_backend_override(var):
    p = _bench(ROOT, {var: "1"})
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in BENCHMARK["paths"]:
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_refuses_an_unknown_device_kind(monkeypatch, capsys):
    import jax

    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""
    assert "TPU v99" in out.err


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_at_tiny_size(trace, capsys):
    cell = tinycell.tiny_cell(CELLS[0])
    rc = run.measure(tinycell.args(CELLS[0], trace=trace), BENCHMARK, cell,
                     tinycell.peak())
    out = capsys.readouterr()
    assert rc == 0
    line = tinycell.last_json(out.out)
    assert list(line)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    for name, m in line["compared"].items():
        assert f"compared {name} {m['value']!r} limit {m['limit']!r}" \
            in out.err
    assert out.err.strip().splitlines()[-1].startswith(
        f"compared {list(line['compared'])[-1]} ")
    assert list(line["compared"])[1:] == [
        n for n in check.NUMBERS if n != "residual"]
    assert "0 programs compiled inside the window" in out.err
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        layer = {m["name"] for m in BENCHMARK["per_layer"]}
        assert set(line["metrics"]) <= layer
    else:
        assert set(line["metrics"]) == END_TO_END
        for m in BENCHMARK["end_to_end"]:
            assert line["metrics"][m["name"]]["unit"] == m["unit"]
    json.dumps(line)
