"""``chip_smoke.py`` at a tiny size on the CPU.

The smoke's phases are plain functions of the model width and D; here
they run with ResNet-18 at width 2 on 8x8 images, with the Pallas kernel
bodies in interpret mode, so a broken phase shows up before it costs
chip time.  ``main()`` itself must refuse to run off the chip.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = dict(hw=8, per_client=64, n_test=32)


def test_kernel_phase_tiny_interpret():
    res = chip_smoke.kernel_phase(5000, 8, interpret=True)
    names = [r["kernel"] for r in res]
    assert len(names) == 19 and all(r["ok"] for r in res)
    assert {"safl_fold_q4", "sdga_aggregate_q8", "screen_rows_q4",
            "safl_aggregate[mix]"} <= set(names)


@pytest.fixture(scope="module")
def tiny_setup():
    return chip_smoke.build_setup(2, **TINY)


@pytest.mark.parametrize("mode,wire", chip_smoke.ENGINE_RUNS,
                         ids=[m if w == "f32" else f"{m}-{w}"
                              for m, w in chip_smoke.ENGINE_RUNS])
def test_engine_run_tiny_interpret(tiny_setup, monkeypatch, mode, wire):
    """Every engine run of the smoke, with its assertions (finite
    metrics, backend, one compile of the server step and of the batched
    client program, none after round 1).  On the CPU the
    compiled server program holds no TPU kernel, so the marker checked
    is only that the program lowers and compiles."""
    monkeypatch.setenv("REPRO_AGG_BACKEND", "pallas_interpret")
    rec = chip_smoke.check_engine_run(
        tiny_setup, mode, wire, backend="pallas_interpret",
        kernel_text="HloModule")
    assert rec["channel"] == ("buffered" if mode in ("SS", "SA")
                              else "streaming")
    assert rec["compiles"]["server_step"] == rec["compiles"]["wave"] == 1


def test_mesh_phase_tiny_subprocess():
    """The ``--chips 4`` phase on four virtual CPU devices: rows on four
    devices, each mesh run matches the one-device server on its own
    uploads, a dropped or doubled upload is seen, and the engines agree
    end to end."""
    code = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {ROOT!r})
        import chip_smoke
        out = chip_smoke.mesh_phase(2, n_dev=4, hw=8, per_client=64,
                                    n_test=32)
        assert len(out) == 3, out
        for name in ("devices=4", "mesh_shape=(2, 2)"):
            o = out[name]
            assert o["replay_diff"] <= chip_smoke.MESH_TOL, o
            assert min(o["dropped_row_diff"], o["doubled_row_diff"]) \
                > 10 * chip_smoke.MESH_TOL, o
        print("MESH_PHASE_OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("REPRO_AGG_BACKEND", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "MESH_PHASE_OK" in out.stdout, (out.stdout[-3000:]
                                           + out.stderr[-3000:])


@pytest.mark.parametrize("case", ["cpu", "override", "alone"])
def test_main_refuses_off_chip(tmp_path, case):
    """No TPU, a backend override, or no repository next to the script:
    exit non-zero and print no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_AGG_BACKEND", None)
    env.pop("REPRO_PALLAS_INTERPRET", None)
    script = os.path.join(ROOT, "chip_smoke.py")
    cwd = ROOT
    if case == "override":
        env["REPRO_AGG_BACKEND"] = "pallas"
    elif case == "alone":
        env.pop("PYTHONPATH", None)
        alone = tmp_path / "chip_smoke.py"
        alone.write_text(open(script).read())
        script, cwd = str(alone), str(tmp_path)
    out = subprocess.run([sys.executable, script], env=env, cwd=cwd,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
