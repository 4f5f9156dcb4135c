"""Sharding-rule unit tests (pure spec logic on a stub mesh) + a subprocess
mini dry-run that exercises the real pjit path on 8 placeholder devices."""
import json
import os
import subprocess
import sys
import textwrap

import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding.rules import add_fsdp, batch_spec, spec_for_path


class StubMesh:
    def __init__(self, **shape):
        self.shape = shape


MESH = StubMesh(data=16, model=16)


@pytest.mark.parametrize("path,shape,want", [
    ("embed", (163840, 7168), P("model", None)),
    ("head", (7168, 163840), P(None, "model")),
    ("layers_dense.attn.wq", (28, 2048, 2048), P(None, None, "model")),
    ("layers_dense.attn.wo", (28, 2048, 2048), P(None, "model", None)),
    ("layers_dense.mlp.w1", (28, 2048, 6144), P(None, None, "model")),
    ("layers_dense.mlp.w2", (28, 6144, 2048), P(None, "model", None)),
    ("layers_dense.ln1.scale", (28, 2048), P(None, None)),
    # zamba2: two leading scan dims (groups x per-group) never sharded
    ("mamba.ssm.in_proj", (9, 5, 2560, 10448), P(None, None, None, "model")),
    # non-divisible dim falls back to replication
    ("layers_dense.attn.wq", (2, 100, 100), P(None, None, None)),
])
def test_megatron_specs(path, shape, want):
    got = spec_for_path(path, shape, MESH, "megatron", False)
    assert tuple(got) == tuple(want), (path, got)


def test_moe_expert_table_sharded_on_experts():
    got = tuple(spec_for_path("layers_moe.moe.w1", (60, 384, 7168, 2048),
                              MESH, "megatron", True))
    assert got == (None, "model", None, None)  # expert dim after scan dim


def test_fsdp_adds_data_axis():
    got = spec_for_path("layers_dense.attn.wq", (28, 7168, 7168), MESH,
                        "fsdp", False)
    assert "model" in tuple(got) and "data" in tuple(got)


def test_fsdp_skips_non_divisible():
    spec = add_fsdp([None, None], (3, 7), 0, MESH)
    assert spec == [None, None]


def test_batch_spec_axes():
    assert tuple(batch_spec(StubMesh(data=16, model=16))) == ("data",)
    multi = batch_spec(StubMesh(pod=2, data=16, model=16))
    assert tuple(multi)[0] == ("pod", "data")


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    """End-to-end pjit lower+compile on 8 placeholder devices (reduced arch,
    2x4 mesh) — validates the full dry-run path without the 512-way cost."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import ARCHS, reduced_config
        from repro.models import build_model
        from repro.sharding import param_specs
        from repro.launch.steps import make_train_step
        from repro.launch.dryrun import collective_bytes
        from repro.launch.mesh import make_auto_mesh
        import dataclasses
        cfg = dataclasses.replace(reduced_config(ARCHS["qwen3-1.7b"]),
                                  d_model=256, n_heads=4, n_kv_heads=2)
        mesh = make_auto_mesh((2, 4), ("data", "model"))
        model = build_model(cfg)
        params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        pspecs = param_specs(params, cfg, mesh)
        step_fn, opt = make_train_step(model, cfg)
        ostate = jax.eval_shape(opt.init, params)
        ospecs = {k: pspecs for k in ostate}
        batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32,
                 sharding=NamedSharding(mesh, P("data", None)))}
        step = jax.ShapeDtypeStruct((), jnp.int32,
                                    sharding=NamedSharding(mesh, P()))
        lowered = jax.jit(step_fn,
                          in_shardings=(pspecs, ospecs, None, None),
                          out_shardings=(pspecs, ospecs, None)
                          ).lower(params, ostate, batch, step)
        compiled = lowered.compile()
        coll = collective_bytes(compiled.as_text())
        assert "all-reduce" in coll and coll["all-reduce"] > 0, coll
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes >= 0
        print("MINI_DRYRUN_OK", sum(coll.values()))
    """)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "MINI_DRYRUN_OK" in out.stdout, out.stderr[-2000:]


def test_dryrun_records_exist_and_pass():
    """If the full dry-run matrix has been produced (launch/dryrun.py --all),
    every record must be OK or the one sanctioned SKIP."""
    d = os.path.join(os.path.dirname(__file__), "..", "experiments",
                     "dryrun")
    if not os.path.isdir(d) or not os.listdir(d):
        pytest.skip("dry-run artifacts not generated yet")
    bad = []
    for f in os.listdir(d):
        if not f.endswith(".json"):
            continue
        rec = json.load(open(os.path.join(d, f)))
        if rec["status"] == "FAIL":
            bad.append((f, rec.get("error", "")[:100]))
        if rec["status"] == "SKIP":
            assert rec["arch"] == "seamless-m4t-medium"
            assert rec["shape"] == "long_500k"
    assert not bad, bad
