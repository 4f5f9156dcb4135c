"""Chip smoke test: drive the SAFL engine's main path on a TPU.

    python chip_smoke.py              # one chip: kernel + engine phases
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

Phases (one process; nothing here starts a child that needs the chip):

* kernel: every Pallas server kernel that ``FlatServer`` calls, at
  ResNet-18 width (D = 11,173,962) and K = 8, against its
  ``repro.kernels.ref`` oracle (the oracle runs at full f32 matmul
  precision).
* engine: ``FLEngine`` training ResNet-18 (width 64) clients on
  CIFAR-10-shaped 32x32 synthetic data in the paper's four modes (SS, SA,
  AS, AA), then AS on the q8 and q4 wires.  Each run checks finite eval
  metrics, the server backend, a ``tpu_custom_call`` in the compiled
  server step or fold, and no recompilation after round 1.
* mesh (``--chips 4``): AS fedsgd with ``devices=4``, with
  ``mesh_shape=(2, 2)`` and on one device, same seed, through the first
  aggregation at full f32 matmul precision.  The channel rows sit on
  four distinct devices; each mesh run's params match the one-device
  server round replayed on that run's own uploads to 1e-4 (a dropped or
  doubled upload fails this), and the one-device engine's to 1e-3.

The phases are plain functions of the model width, D and the backend, so
a CPU test runs them at a tiny size.  ``main()`` alone insists on a TPU:
it exits non-zero, printing no result, off the chip or when a backend
override (``REPRO_AGG_BACKEND``, ``REPRO_PALLAS_INTERPRET``) is set.  Its
last stdout line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
if os.path.isdir(os.path.join(ROOT, "src", "repro")):
    sys.path.insert(0, os.path.join(ROOT, "src"))

#: ResNet-18 at width 64 with a 10-class head: the paper's CIFAR model
RESNET18_D = 11_173_962
OVERRIDES = ("REPRO_AGG_BACKEND", "REPRO_PALLAS_INTERPRET")
#: the engine runs of the engine phase: paper mode (configs.paper.MODES)
#: and upload wire
ENGINE_RUNS = (("SS", "f32"), ("SA", "f32"), ("AS", "f32"), ("AA", "f32"),
               ("AS", "q8"), ("AS", "q4"))
#: aggregation rounds of each engine-phase run
ENGINE_ROUNDS = 3
MESH_TOL = 1e-4  # as tests/test_multidevice.py's engine parity (CPU)
#: end-to-end mesh vs one device, client training included (the mesh
#: phase's docstring says why this is looser than MESH_TOL)
E2E_TOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# kernel phase
# ---------------------------------------------------------------------------


def kernel_cases(d: int, k: int, *, seed: int = 0, qblock: int = 512,
                 interpret: bool = False) -> tuple:
    """``(cases, data)``: one ``(name, kernel_fn, oracle_fn, tol)`` case
    per server kernel the engine reaches, on seeded ``data`` of width
    ``d`` and ``k`` rows (both functions take the ``data`` dict).  The
    kernels weigh rows as the engine does (``discount="none"``: the
    engine composes the final weights on the host)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels import safl_agg as kern

    qb = qblock
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    u = jax.random.normal(ks[0], (k, d), jnp.float32) * 0.1
    p = jax.random.normal(ks[1], (d,), jnp.float32)
    w = jax.random.uniform(ks[4], (k,), jnp.float32, 0.1, 1.0)
    nb = -(-d // qb)
    dq = nb * qb
    blocks = jnp.pad(u, ((0, 0), (0, dq - d))).reshape(k * nb, qb)
    q8, s8 = ref.quantize_ref(blocks)
    q4, s4 = ref.quantize_q4_ref(
        blocks, jax.random.uniform(ks[5], blocks.shape, jnp.float32))
    acc = jax.random.normal(ks[6], (d,), jnp.float32)
    # every array travels as a jit argument (closed-over arrays would be
    # baked into the program as constants)
    data = dict(
        u=u, p=p, w=w, mixc=w / (2 * k),  # mix coefficients sum below 1
        mom=jax.random.normal(ks[2], (d,), jnp.float32) * 0.01,
        ema=p + jax.random.normal(ks[3], (d,), jnp.float32) * 0.01,
        q8=q8.reshape(k, dq), s8=s8.reshape(k, nb),
        p4=ref.pack_q4_ref(q4.reshape(k, dq)), s4=s4.reshape(k, nb),
        acc=acc, accq=jnp.pad(acc, (0, dq - d)))
    lr = 0.1
    sd = dict(server_lr=lr, momentum=0.8, ema_anchor=0.05, ema_decay=0.95)
    ki = dict(interpret=interpret)
    kq = dict(qblock=qb, interpret=interpret)

    def sdga_ref(a, mean):
        return ref.sdga_step_from_mean(mean, a["p"], a["mom"], a["ema"],
                                       **sd)

    def sdga_kernel(fn, *rows, **kw):
        return lambda a: fn(*(a[r] for r in rows), a["w"], a["p"],
                            a["mom"], a["ema"], discount="none", **kw, **sd)

    # tolerance on max|kernel - oracle| / max(1, max|oracle|).  The K-row
    # reductions and elementwise steps differ from the oracle only in
    # summation order; a screen sums a whole D-long row, hence 1e-4.
    t, tr = 1e-5, 1e-4
    cases = [
        ("safl_fold",
         lambda a: kern.safl_fold(a["acc"], a["u"][0], a["w"][0], **ki),
         lambda a: ref.fold_ref(a["acc"], a["u"][0], a["w"][0]), t),
        ("safl_fold_q8",
         lambda a: kern.safl_fold_q8(a["accq"], a["q8"][0], a["s8"][0],
                                     a["w"][0], **kq),
         lambda a: ref.fold_q8_ref(a["accq"], a["q8"][0], a["s8"][0],
                                   a["w"][0], qb), t),
        ("safl_fold_q4",
         lambda a: kern.safl_fold_q4(a["accq"], a["p4"][0], a["s4"][0],
                                     a["w"][0], **kq),
         lambda a: ref.fold_q4_ref(a["accq"], a["p4"][0], a["s4"][0],
                                   a["w"][0], qb), t),
        ("safl_aggregate[fedsgd]",
         lambda a: kern.safl_aggregate(a["u"], a["w"], a["p"], lr,
                                       mode="fedsgd", **ki),
         lambda a: ref.safl_agg_ref(a["u"], a["w"], a["p"], lr), t),
        ("safl_aggregate[avg]",
         lambda a: kern.safl_aggregate(a["u"], a["w"], mode="avg", **ki),
         lambda a: ref.weighted_avg_ref(a["u"], a["w"]), t),
        ("safl_aggregate[mix]",
         lambda a: kern.safl_aggregate(a["u"], a["mixc"], a["p"],
                                       mode="mix", **ki),
         lambda a: ref.fedasync_flat_ref(a["u"], a["mixc"], a["p"]), t),
        ("safl_aggregate[sum]",
         lambda a: kern.safl_aggregate(a["u"], a["w"], mode="sum", **ki),
         lambda a: ref.weighted_sum_ref(a["u"], a["w"]), t),
        ("sdga_aggregate", sdga_kernel(kern.sdga_aggregate, "u", **ki),
         lambda a: sdga_ref(a, ref.weighted_avg_ref(a["u"], a["w"])), t),
        ("screen_rows", lambda a: kern.screen_rows(a["u"], **ki),
         lambda a: ref.screen_sumsq_ref(a["u"]), tr),
    ]
    for wire, q, s, agg, wsum, wavg in (
            ("q8", "q8", "s8", kern.safl_aggregate_q8,
             lambda *x: ref.weighted_sum_q8_ref(*x, int8_dot=False),
             ref.weighted_avg_q8_ref),
            ("q4", "p4", "s4", kern.safl_aggregate_q4,
             ref.weighted_sum_q4_ref, ref.weighted_avg_q4_ref)):
        sdga_k = getattr(kern, f"sdga_aggregate_{wire}")
        screen_k = getattr(kern, f"screen_rows_{wire}")
        screen_r = getattr(ref, f"screen_sumsq_{wire}_ref")
        cases += [
            (f"safl_aggregate_{wire}[fedsgd]",
             lambda a, q=q, s=s, agg=agg: agg(
                 a[q], a[s], a["w"], a["p"], lr, mode="fedsgd", **kq),
             lambda a, q=q, s=s, wavg=wavg: (
                 a["p"] - lr * wavg(a[q], a[s], a["w"], qb)[:d]), t),
            (f"safl_aggregate_{wire}[avg]",
             lambda a, q=q, s=s, agg=agg: agg(a[q], a[s], a["w"],
                                              mode="avg", **kq),
             lambda a, q=q, s=s, wavg=wavg: wavg(a[q], a[s], a["w"], qb),
             t),
            (f"safl_aggregate_{wire}[sum]",
             lambda a, q=q, s=s, agg=agg: agg(a[q], a[s], a["w"],
                                              mode="sum", **kq),
             lambda a, q=q, s=s, wsum=wsum: wsum(a[q], a[s], a["w"], qb),
             t),
            (f"sdga_aggregate_{wire}", sdga_kernel(sdga_k, q, s, **kq),
             lambda a, q=q, s=s, wavg=wavg: sdga_ref(
                 a, wavg(a[q], a[s], a["w"], qb)[:d]), t),
            (f"screen_rows_{wire}",
             lambda a, q=q, s=s, fn=screen_k: fn(a[q], a[s], **kq),
             lambda a, q=q, s=s, fn=screen_r: fn(a[q], a[s], qb), tr),
        ]
    return cases, data


def kernel_phase(d: int, k: int, *, seed: int = 0,
                 interpret: bool = False) -> list:
    """Every server kernel at width ``d`` against its oracle.  Returns
    one result dict per kernel; raises on the first mismatch."""
    import jax
    import jax.numpy as jnp

    results = []
    cases, data = kernel_cases(d, k, seed=seed, interpret=interpret)
    for name, kfn, rfn, tol in cases:
        t0 = time.perf_counter()
        got = jax.block_until_ready(jax.jit(kfn)(data))
        first_s = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = jax.jit(rfn)(data)
        err = scale = 0.0
        finite = True
        for g, r in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert g.shape == r.shape, (name, g.shape, r.shape)
            err = max(err, float(jnp.max(jnp.abs(g - r))))
            scale = max(scale, float(jnp.max(jnp.abs(r))))
            finite = finite and bool(jnp.all(jnp.isfinite(g)))
        rel = err / max(1.0, scale)
        ok = finite and rel <= tol
        results.append(dict(kernel=name, rel_err=rel, tol=tol,
                            first_call_s=first_s, ok=ok))
        log(f"kernel {name}: rel_err={rel:.3e} (tol {tol:g}) first call "
            f"{first_s:.3f}s {'ok' if ok else 'FAILED'}")
        assert ok, f"kernel {name} disagrees with its oracle: {rel} > {tol}"
    return results


# ---------------------------------------------------------------------------
# engine phase
# ---------------------------------------------------------------------------


def build_setup(width: int, *, seed: int = 0, hw: int = 32,
                n_clients: int = 16, per_client: int = 64,
                n_test: int = 256) -> dict:
    """ResNet-18 at ``width`` + seeded CIFAR-10-shaped data, split evenly
    over ``n_clients``.  One ``apply_fn`` serves every engine, so runs
    over the same model share their memoized client programs."""
    import jax

    from repro.data import build_client_shards, make_dataset
    from repro.data.synthetic import Dataset
    from repro.models.vision_cnn import build_paper_model

    n_train = n_clients * per_client
    ds = make_dataset("cifar10", n=n_train + n_test, seed=seed, hw=hw)
    tr = Dataset(ds.x[:n_train], ds.y[:n_train], ds.n_classes, ds.kind)
    shards = build_client_shards(tr, "iid", n_clients, 32, seed=seed)
    p0, s0, apply_fn = build_paper_model(
        "resnet18", jax.random.PRNGKey(seed), width=width,
        n_classes=ds.n_classes, in_ch=3)
    return dict(p0=p0, s0=s0, apply_fn=apply_fn, shards=shards,
                test_x=ds.x[n_train:], test_y=ds.y[n_train:],
                kind=ds.kind, n_clients=n_clients)


def make_engine(setup: dict, mode: str, wire: str = "f32", *, k: int = 8,
                seed: int = 0, **overrides):
    """An ``FLEngine`` in paper mode ``mode`` (``configs.paper.MODES``).
    Equal client speeds and near-zero link times keep each early horizon
    to ``k`` distinct clients, so one wave size serves every round."""
    from repro.configs.paper import MODES
    from repro.core import FLEngine

    base = MODES[mode]
    cfg = dataclasses.replace(
        base, n_clients=setup["n_clients"], k=k, wire=wire, seed=seed,
        client_lr=0.05,
        server_lr=0.05 if base.aggregation == "fedsgd" else 1.0,
        speed_sigma=0.0, comm_mean_s=0.01, **overrides)
    return FLEngine(cfg, setup["apply_fn"], setup["kind"], setup["p0"],
                    setup["s0"], setup["shards"], setup["test_x"],
                    setup["test_y"])


def run_to(eng, rounds: int):
    """Run ``eng`` until it has aggregated ``rounds`` times in all
    (``FLEngine.run`` counts sync rounds per call, semi-async rounds in
    total)."""
    n = rounds - eng.t_global if eng.cfg.mode == "sync" else rounds
    return eng.run(n)


def server_program_text(eng) -> str:
    """Optimized HLO of the server program the engine's channel runs: the
    streaming fold, or the buffered step."""
    import jax
    import jax.numpy as jnp

    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                         sharding=a.sharding)
    srv, codec = eng._server, eng.codec
    if eng._streaming:
        bank = eng._accum._bank
        if srv.wire == "f32":
            payload = (jax.ShapeDtypeStruct((bank.shape[1],), jnp.float32),)
        else:
            nq = codec.dq // (2 if srv.wire == "q4" else 1)
            payload = (jax.ShapeDtypeStruct((nq,), jnp.int8),
                       jax.ShapeDtypeStruct((codec.n_qblocks,),
                                            jnp.float32))
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        lowered = srv.fold_program.lower(
            sds(bank), *payload, jax.ShapeDtypeStruct((), jnp.int32),
            scalar, scalar)
    else:
        buf = (eng._qbuf.views if eng._qbuf is not None else eng._buf)
        k = jax.tree_util.tree_leaves(buf)[0].shape[0]
        lowered = srv._fn.lower(
            sds(eng._flat_params), jax.tree_util.tree_map(sds, buf),
            jax.ShapeDtypeStruct((k,), jnp.float32),
            jax.tree_util.tree_map(sds, eng._opt))
    return lowered.compile().as_text()


def check_engine_run(setup: dict, mode: str, wire: str, *, backend: str, kernel_text: str | None,
                     expect_d: int | None = None, seed: int = 0) -> dict:
    """One engine run of ``ENGINE_ROUNDS`` rounds with the smoke's
    assertions; returns its record.

    ``backend`` is the ``FlatServer`` backend the run must pick;
    ``kernel_text`` the marker its compiled server program must hold
    (``"tpu_custom_call"`` on the chip; None skips the check)."""
    import numpy as np

    from repro.obs.profile import engine_compile_log

    rounds = ENGINE_ROUNDS
    name = mode if wire == "f32" else f"{mode}-{wire}"
    t0 = time.perf_counter()
    eng = make_engine(setup, mode, wire, seed=seed)
    t_build = time.perf_counter() - t0
    if expect_d is not None:
        assert eng.codec.d == expect_d, (name, eng.codec.d, expect_d)
    assert eng._server.backend == backend, (name, eng._server.backend)
    t0 = time.perf_counter()
    run_to(eng, 1)
    t_first = time.perf_counter() - t0
    after_first = engine_compile_log(eng).counts()
    t0 = time.perf_counter()
    res = run_to(eng, rounds)
    t_rest = time.perf_counter() - t0
    counts = engine_compile_log(eng).counts()
    # no program compiles after round 1; the server step and the
    # batched client program (sync round or semi-async wave) compiled once
    assert counts == after_first, (name, after_first, counts)
    assert counts["server_step"] == 1 and counts["wave"] == 1, \
        (name, counts)
    recs = res.metrics.records
    assert len(recs) == rounds, (name, len(recs))
    vals = [v for r in recs for v in (r.accuracy, r.loss, r.update_norm)]
    assert np.all(np.isfinite(vals)), (name, vals)
    assert res.metrics.summary()["nan_rounds"] == 0, name
    assert np.all(np.isfinite(np.asarray(eng._flat_params))), name
    has_kernel = None
    if kernel_text is not None:
        has_kernel = kernel_text in server_program_text(eng)
        assert has_kernel, f"{name}: no {kernel_text} in the server program"
    rec = dict(run=name, d=eng.codec.d, channel=eng._channel,
               backend=eng._server.backend, kernel_in_server=has_kernel,
               compiles=counts, build_s=t_build, first_round_s=t_first,
               warm_rounds=rounds - 1,
               warm_round_s=t_rest / max(rounds - 1, 1),
               final_accuracy=float(recs[-1].accuracy),
               final_loss=float(recs[-1].loss))
    log(f"engine {name}: D={eng.codec.d} channel={eng._channel} "
        f"backend={eng._server.backend} kernel={has_kernel} "
        f"compiles={counts} build {t_build:.2f}s round 1 {t_first:.2f}s "
        f"warm round {rec['warm_round_s']:.3f}s "
        f"acc={rec['final_accuracy']:.4f} loss={rec['final_loss']:.4f}")
    return rec


def engine_phase(width: int, *, backend: str = "pallas",
                 kernel_text: str | None = "tpu_custom_call",
                 expect_d: int | None = None, seed: int = 0,
                 **setup_kw) -> list:
    """The paper's four modes, then AS on each lossy dense wire."""
    setup = build_setup(width, seed=seed, **setup_kw)
    return [check_engine_run(setup, mode, wire, backend=backend,
                             kernel_text=kernel_text, expect_d=expect_d,
                             seed=seed)
            for mode, wire in ENGINE_RUNS]


# ---------------------------------------------------------------------------
# mesh phase
# ---------------------------------------------------------------------------


def record_folds(eng) -> list:
    """Record every upload ``eng`` folds into its streaming accumulator,
    as ``(host payload, weight, beta)`` in arrival order."""
    import numpy as np

    folds = []
    fold = eng._accum.fold

    def recording(payload, *, w, beta=1.0, shard=0, staleness=0):
        folds.append((tuple(np.asarray(a) for a in payload), w, beta))
        fold(payload, w=w, beta=beta, shard=shard, staleness=staleness)

    eng._accum.fold = recording
    return folds


def replay(eng, p0, folds):
    """The one-device engine ``eng``'s server round on recorded uploads:
    its own fold program into a fresh one-row bank, then its finalize
    from the flat params ``p0``."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.flatbuf import AccumBuffer

    srv = eng._server
    acc = AccumBuffer(eng._accum.d, srv.fold_program)
    for payload, w, beta in folds:
        acc.fold(tuple(jnp.asarray(a) for a in payload), w=w, beta=beta)
    bank, wvec, stats = acc.seal()
    p0 = jnp.asarray(p0)
    params, _, _, _ = srv.finalize(p0, bank, wvec, srv.init_opt(p0),
                                   pprod=stats["pprod"])
    return np.asarray(params)


def mesh_phase(width: int, *, n_dev: int = 4, seed: int = 0,
               expect_d: int | None = None, **setup_kw) -> dict:
    """AS fedsgd through its first aggregation on one device, with
    ``devices=n_dev`` and with the ``(2, n_dev // 2)`` edge/pod mesh,
    same seed.  Every upload each engine folds is recorded.

    * layout: a mesh run keeps one channel row on each of ``n_dev``
      devices;
    * server path: its params match the one-device server round replayed
      on the same uploads to ``MESH_TOL``, and that check fails when one
      upload is dropped or folded twice;
    * end to end: its params lie within ``E2E_TOL`` of the one-device
      engine's.

    Only the end-to-end check sees client training, whose wave lowers
    differently on ``n_dev`` devices than on one (lanes per device,
    hence the conv tiling), so the uploads themselves differ by float
    rounding; their gap is reported.  The first aggregation already runs
    every mesh stage: the sharded client wave, one bank row per device,
    the shard-local fold kernel, the cross-shard sum and the server
    step.  All runs use full f32 matmul precision, as the TPU's default
    one-pass bf16 would turn rounding differences into bf16 ones."""
    import jax
    import numpy as np

    setup = build_setup(width, seed=seed, **setup_kw)
    params, folds, out = {}, {}, {}

    def run(name, **kw):
        t0 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            eng = make_engine(setup, "AS", seed=seed, **kw)
            if expect_d is not None:
                assert eng.codec.d == expect_d, (name, eng.codec.d)
            p0 = np.asarray(eng._flat_params)
            folds[name] = record_folds(eng)
            run_to(eng, 1)
            params[name] = np.asarray(eng._flat_params)
        wall = time.perf_counter() - t0
        shards = eng._accum._bank.addressable_shards
        out[name] = dict(wall_s=wall,
                         row_devices=sorted(str(s.device) for s in shards),
                         rows=[s.data.shape[0] for s in shards])
        return eng, p0

    def gap(a, b):
        return float(np.max(np.abs(a - b)))

    one = "1 device"
    eng1, p0 = run(one)
    layouts = (f"devices={n_dev}", f"mesh_shape=(2, {n_dev // 2})")
    run(layouts[0], devices=n_dev)
    run(layouts[1], mesh_shape=(2, n_dev // 2))
    k = len(folds[one])
    with jax.default_matmul_precision("highest"):
        out[one]["replay_diff"] = gap(replay(eng1, p0, folds[one]),
                                      params[one])
        for name in layouts:
            f = folds[name]
            o = out[name]
            o["replay_diff"] = gap(replay(eng1, p0, f), params[name])
            o["dropped_row_diff"] = gap(replay(eng1, p0, f[:-1]),
                                        params[name])
            o["doubled_row_diff"] = gap(replay(eng1, p0, f[:1] + f),
                                        params[name])
            o["e2e_diff"] = gap(params[name], params[one])
            o["upload_rel_gap"] = max(
                float(np.linalg.norm(u - v) / np.linalg.norm(v))
                for ((u,), _, _), ((v,), _, _) in zip(f, folds[one]))
    # every run is reported before the first assertion fails
    for name in (one,) + layouts:
        o = out[name]
        log(f"mesh {name}: wall {o['wall_s']:.2f}s uploads "
            f"{len(folds[name])} rows {o['rows']} on {o['row_devices']} "
            + " ".join(f"{key}={v:.3e}" for key, v in o.items()
                       if key.endswith(("_diff", "_gap"))))
    assert np.all(np.isfinite(params[one])) and k > 1, (k,)
    assert out[one]["replay_diff"] <= MESH_TOL, out[one]
    for name in layouts:
        o = out[name]
        assert np.all(np.isfinite(params[name])), name
        assert len(folds[name]) == k, (name, len(folds[name]), k)
        assert (len(set(o["row_devices"])) == n_dev
                and o["rows"] == [1] * n_dev), (name, o)
        assert o["replay_diff"] <= MESH_TOL, (name, o)
        assert min(o["dropped_row_diff"], o["doubled_row_diff"]) \
            > MESH_TOL, (name, o)
        assert o["e2e_diff"] <= E2E_TOL, (name, o)
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: kernel + engine phases on one chip; 4: the "
                         "mesh phase alone, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    for var in OVERRIDES:
        if var in os.environ:
            print(f"chip_smoke: {var} is set; the smoke runs the backend "
                  "the chip selects, unset it", file=sys.stderr)
            return 2
    try:
        import jax

        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repository ({e})",
              file=sys.stderr)
        return 2
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              "devices", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    log(f"device {dev.device_kind} x{len(devs)}  jax {jax.__version__}  "
        f"compile cache {cache}")
    t_all = time.perf_counter()
    if args.chips == 4:
        mesh_phase(64, n_dev=4, seed=args.seed, expect_d=RESNET18_D)
    else:
        t0 = time.perf_counter()
        kernel_phase(RESNET18_D, 8, seed=args.seed)
        log(f"kernel phase {time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        engine_phase(64, seed=args.seed, expect_d=RESNET18_D)
        log(f"engine phase {time.perf_counter() - t0:.1f}s")
    log(f"total {time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
