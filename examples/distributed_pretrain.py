"""Distributed pretraining demo: the REAL pjit path on a multi-device mesh
(8 placeholder CPU devices), with the paper's FL aggregation as the
cross-pod step — the miniature of the production 2x16x16 deployment.

Spawns itself with XLA_FLAGS so the parent process keeps 1 device.

Run:  PYTHONPATH=src python examples/distributed_pretrain.py [--steps 20]
"""
import argparse
import os
import subprocess
import sys

INNER = "REPRO_DISTRIBUTED_INNER"


def inner():
    import dataclasses
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import ARCHS, reduced_config
    from repro.launch.mesh import make_auto_mesh
    from repro.launch.steps import make_fl_train_step
    from repro.models import build_model
    from repro.sharding import param_specs

    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--aggregation", default="fedsgd",
                    choices=["fedsgd", "fedavg"])
    args = ap.parse_args()

    cfg = dataclasses.replace(reduced_config(ARCHS["qwen3-1.7b"]),
                              d_model=256, n_heads=4, n_kv_heads=2)
    mesh = make_auto_mesh((2, 2, 2), ("pod", "data", "model"))
    print(f"devices={len(jax.devices())} mesh={dict(mesh.shape)} "
          f"aggregation={args.aggregation}")

    model = build_model(cfg)
    n_pods = mesh.shape["pod"]
    step_fn, opt = make_fl_train_step(
        model, cfg, aggregation=args.aggregation, lr=5e-3,
        inner_steps=2 if args.aggregation == "fedavg" else 1)

    params = model.init(jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n_pods,) + x.shape), params)
    pspecs = jax.tree_util.tree_map(
        lambda ns: NamedSharding(mesh, P("pod", *ns.spec)),
        param_specs(jax.tree_util.tree_map(lambda x: x[0], params), cfg,
                    mesh))
    params = jax.device_put(params, pspecs)
    ostate = jax.vmap(opt.init)(params)

    rng = np.random.default_rng(0)
    B, S = 8, 32
    bspec = NamedSharding(mesh, P(("pod", "data"), None))
    weights = jnp.ones((n_pods,))
    jstep = jax.jit(step_fn, donate_argnums=(0, 1))
    t0 = time.time()
    for step in range(args.steps):
        toks = jax.device_put(
            jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
            bspec)
        params, ostate, m = jstep(params, ostate, {"tokens": toks},
                                  jnp.int32(step), weights)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:3d} loss {float(m['loss']):.4f}")
    # pod replicas stay in sync after aggregation (FedSGD) / averaging
    leaf = jax.tree_util.tree_leaves(params)[0]
    drift = float(jnp.max(jnp.abs(leaf[0] - leaf[1])))
    print(f"cross-pod param drift after aggregation: {drift:.2e}")
    assert drift < 1e-4, "pods diverged — aggregation broken"
    print(f"distributed_pretrain OK ({time.time()-t0:.1f}s)")


def main():
    if os.environ.get(INNER):
        inner()
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env[INNER] = "1"
    env.setdefault("PYTHONPATH", "src")
    ret = subprocess.run([sys.executable, __file__] + sys.argv[1:],
                         env=env)
    sys.exit(ret.returncode)


if __name__ == "__main__":
    main()
