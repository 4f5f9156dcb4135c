"""Paper-experiment launcher: one SAFL/SFL run from the command line.

    PYTHONPATH=src python -m repro.launch.fl_sim --dataset cifar10 \
        --model cnn --dist hetero_dirichlet --alpha 0.3 \
        --mode semi_async --aggregation fedsgd --rounds 30
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import numpy as np

from repro.configs.base import FLConfig
from repro.core import FLEngine
from repro.data import build_client_shards, make_dataset, train_test_split
from repro.launch.compile_cache import enable_compile_cache
from repro.models.lstm import build_lstm
from repro.models.vision_cnn import build_paper_model
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import profile as obs_profile

#: --json-out summary schema version (bumped on breaking shape changes)
SUMMARY_SCHEMA = 1


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="cifar10",
                    choices=["cifar10", "cifar100", "femnist",
                             "shakespeare", "sentiment140"])
    ap.add_argument("--model", default="cnn",
                    choices=["cnn", "resnet18", "vgg16", "lstm"])
    ap.add_argument("--dist", default="hetero_dirichlet")
    ap.add_argument("--alpha", type=float, default=0.3)
    ap.add_argument("--sigma", type=float, default=0.5)
    ap.add_argument("--n-labels", type=int, default=2)
    ap.add_argument("--mode", default="semi_async",
                    choices=["sync", "semi_async"])
    ap.add_argument("--aggregation", default="fedsgd")
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--samples", type=int, default=2000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compress", action="store_true",
                    help="int8 quantized upload channel (error-feedback "
                         "residuals on gradient targets); legacy alias "
                         "for --wire q8")
    ap.add_argument("--wire", default="f32",
                    choices=["f32", "q8", "q4", "topk"],
                    help="upload wire format: f32 (dense rows), q8 "
                         "(per-block int8), q4 (packed two-lane int4 "
                         "with stochastic rounding — the SR key is "
                         "fold_in(fold_in(PRNGKey(seed), cid), per-"
                         "client upload counter), so sequential and "
                         "batched engines stay bit-identical), topk "
                         "(sparse (indices, values) rows, gradient "
                         "aggregations only; dropped coordinates feed "
                         "the error-feedback residual)")
    ap.add_argument("--topk-frac", type=float, default=0.1,
                    help="--wire topk: fraction of coordinates kept per "
                         "upload (rounded up to a whole quant block)")
    ap.add_argument("--eval-every", type=int, default=1,
                    help="evaluate every Nth aggregation round (the final "
                         "round is always evaluated); >1 thins the metric "
                         "curve but skips the per-round eval compute")
    ap.add_argument("--sequential", action="store_true",
                    help="force the sequential per-upload engine path "
                         "(batch_clients=False) — the parity oracle for "
                         "the default horizon-batched execution")
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the flat upload channel and the batched "
                         "waves over this many devices (mesh 'pod' axis; "
                         "requires k %% devices == 0; on CPU hosts set "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N before launching)")
    ap.add_argument("--mesh", type=int, nargs=2, default=None,
                    metavar=("E", "P"),
                    help="hierarchical 2-D (edge, pod) aggregation mesh: "
                         "per-shard partials tree-reduce within each of "
                         "the E edge groups over the P-device pod "
                         "sub-axis, then one cross-edge psum of E edge "
                         "partials reaches the server step (cross-edge "
                         "traffic drops ~P x vs the flat mesh); needs "
                         "E*P devices and k %% (E*P) == 0; --mesh 1 P "
                         "is the bit-exact alias of --devices P")
    ap.add_argument("--wave-impl", default="auto",
                    choices=["auto", "vmap", "map"],
                    help="batched-wave lane execution: vmap (vectorized), "
                         "map (lax.map serial lanes, one dispatch — "
                         "avoids the grouped-conv lowering penalty for "
                         "conv models on CPU), auto (per model/backend)")
    ap.add_argument("--no-wave-buckets", action="store_true",
                    help="disable power-of-two wave-size bucketing "
                         "(compile one program per distinct wave size — "
                         "the bucketing parity oracle)")
    ap.add_argument("--sched-timing", default="static",
                    choices=["static", "lognormal", "markov"],
                    help="device-time model (repro.sched.timing): static "
                         "(deterministic, the paper's implicit model), "
                         "lognormal (heavy-tailed per-epoch compute "
                         "jitter), markov (drop-out/rejoin availability "
                         "on top of the jitter)")
    ap.add_argument("--horizon", default="k",
                    choices=["k", "queue", "timeout", "hybrid"],
                    help="aggregation-horizon trigger (semi-async): k "
                         "(the paper's buffered-K rule), queue "
                         "(--horizon-queue admitted uploads), timeout "
                         "(first upload after --horizon-timeout-s "
                         "simulated seconds since the last aggregation; "
                         "streaming channel only), hybrid (whichever of "
                         "queue/timeout fires first)")
    ap.add_argument("--horizon-queue", type=int, default=0,
                    help="queue/hybrid horizons: admitted uploads per "
                         "aggregation (0 -> k)")
    ap.add_argument("--horizon-timeout-s", type=float, default=0.0,
                    help="timeout/hybrid horizons: simulated seconds "
                         "between aggregations")
    ap.add_argument("--server-channel", default="auto",
                    choices=["auto", "streaming", "buffered"],
                    help="server upload channel: streaming folds each "
                         "upload into an O(D) running sum on arrival "
                         "(accumulate-at-ingest; the fold kernel follows "
                         "REPRO_AGG_BACKEND=pallas|ref like every "
                         "aggregation program), buffered keeps the "
                         "(K, D) resident rows — the bit-exact parity "
                         "oracle; auto = streaming for semi_async, "
                         "buffered for sync")
    ap.add_argument("--sched-policy", default="full",
                    choices=["full", "uniform", "seafl", "fedqs",
                             "ratelimit"],
                    help="participation policy (repro.sched.policy): "
                         "full, uniform C-of-N sampling (--sched-c), "
                         "seafl staleness-capped selective training "
                         "(--sched-stale-cap), fedqs adaptive "
                         "staleness x sample-count reweighting, "
                         "ratelimit FedBuff-style server back-pressure "
                         "(--sched-rate-limit; idled clients keep "
                         "training and retry)")
    ap.add_argument("--sched-rate-limit", type=int, default=0,
                    help="ratelimit policy: admitted uploads per round "
                         "before the server answers IDLE (0 -> k); must "
                         "cover the horizon target under count-triggered "
                         "horizons — back-pressure bites with "
                         "--horizon timeout/hybrid")
    ap.add_argument("--sched-c", type=int, default=0,
                    help="uniform policy: clients admitted per round "
                         "(0 = all -> identical to full)")
    ap.add_argument("--sched-stale-cap", type=int, default=4,
                    help="seafl policy: max admissible projected "
                         "staleness")
    ap.add_argument("--sched-jitter-sigma", type=float, default=0.25,
                    help="lognormal/markov: per-epoch compute jitter "
                         "sigma")
    ap.add_argument("--sched-drop-p", type=float, default=0.1,
                    help="markov: P(go offline) after each upload")
    ap.add_argument("--sched-seed", type=int, default=0,
                    help="PRNG seed for timing jitter + policy sampling")
    ap.add_argument("--fault-crash-p", type=float, default=0.0,
                    help="fault layer (repro.faults): P(client crashes "
                         "mid-round) per upload attempt; crashed clients "
                         "resync to the global model and retry after "
                         "exponential backoff")
    ap.add_argument("--fault-straggler-p", type=float, default=0.0,
                    help="P(transient straggler spike) per upload — the "
                         "upload's compute time is multiplied by the "
                         "config's fault_straggler_mult")
    ap.add_argument("--fault-corrupt-p", type=float, default=0.0,
                    help="P(payload corruption) per upload: NaN/Inf lanes "
                         "on the f32 wire, bit flips + a poisoned scale "
                         "block on q8/q4/topk")
    ap.add_argument("--fault-byzantine-p", type=float, default=0.0,
                    help="P(Byzantine upload): sign-flipped and rescaled "
                         "by fault_byzantine_rescale")
    ap.add_argument("--fault-seed", type=int, default=7,
                    help="fault-schedule PRNG seed (counter-keyed per "
                         "(client, upload attempt) — identical schedules "
                         "on the sequential and batched engines)")
    ap.add_argument("--defense", default="none",
                    choices=["none", "screen", "clip"],
                    help="server-side defense: screen drops non-finite / "
                         "over-norm uploads before they touch the "
                         "aggregate, clip rescales over-norm uploads to "
                         "the cap (non-finite still dropped)")
    ap.add_argument("--defense-norm-cap", type=float, default=0.0,
                    help="per-upload L2 norm threshold for screen/clip "
                         "(0 with --defense screen = integrity-only: "
                         "drop non-finite payloads)")
    ap.add_argument("--ckpt-dir", default="",
                    help="engine snapshot directory; with --ckpt-every "
                         "the run is segmented and snapshotted so a "
                         "killed run resumes bit-exactly via --resume")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="snapshot every N aggregation rounds (0 = only "
                         "at run end when --ckpt-dir is set)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest snapshot from --ckpt-dir "
                         "before running (no-op if none exists)")
    ap.add_argument("--trace-dir", default="",
                    help="observability (repro.obs): write the span trace "
                         "into this directory — trace.jsonl (raw spans), "
                         "trace.json (Chrome-trace/Perfetto export), "
                         "metrics.prom / metrics.json (registry "
                         "snapshots); render with python -m "
                         "repro.obs.report <dir>/trace.jsonl")
    ap.add_argument("--trace-level", default="",
                    choices=["", "off", "round", "upload"],
                    help="span detail: round (horizon spans only) or "
                         "upload (full per-upload lifecycle); default "
                         "upload when --trace-dir is given, else off")
    ap.add_argument("--trace-jax", action="store_true",
                    help="additionally wrap the run in a jax.profiler "
                         "trace written into --trace-dir (XLA-level "
                         "timing, viewable in xprof or TensorBoard)")
    ap.add_argument("--json-out", default="")
    args = ap.parse_args()
    enable_compile_cache()
    trace_level = args.trace_level or ("upload" if args.trace_dir
                                       else "off")

    mk_kw = {"hw": 16} if "cifar" in args.dataset or \
        args.dataset == "femnist" else {}
    ds = make_dataset(args.dataset, n=args.samples, seed=args.seed, **mk_kw)
    if args.dataset == "femnist":
        ds.x = np.repeat(ds.x, 3, axis=-1)
    tr, te = train_test_split(ds)
    dist_kw = {}
    if "dirichlet" in args.dist:
        dist_kw = ({"alpha": args.alpha} if args.dist == "hetero_dirichlet"
                   else {"sigma": args.sigma})
    if args.dist == "shards":
        dist_kw = {"n_labels": args.n_labels}
    shards = build_client_shards(tr, args.dist, args.clients, 32,
                                 seed=args.seed, **dist_kw)

    rk = jax.random.PRNGKey(0)
    if args.model == "lstm":
        task = "char" if ds.kind == "char" else "sentiment"
        kw = dict(embed=32, hidden=64)
        if task == "char":
            kw.update(vocab=80, n_out=80)
        p0, s0, fn = build_lstm(rk, task, **kw)
    else:
        mkw = dict(n_classes=ds.n_classes, in_ch=3)
        if args.model == "cnn":
            mkw.update(width=8, image_size=16)
        elif args.model == "resnet18":
            mkw.update(width=8)
        else:
            mkw.update(width_mult=0.125, image_size=32)
        p0, s0, fn = build_paper_model(args.model, rk, **mkw)

    slr = {"fedsgd": 0.05, "sdga": 0.05, "fedbuff": 0.05,
           "fedopt": 0.005}.get(args.aggregation, 1.0)
    cfg = FLConfig(n_clients=args.clients, k=args.k, mode=args.mode,
                   aggregation=args.aggregation, client_lr=0.05,
                   server_lr=slr, seed=args.seed, speed_sigma=0.8,
                   compress_updates=args.compress,
                   wire=args.wire, topk_frac=args.topk_frac,
                   eval_every=args.eval_every,
                   batch_clients=not args.sequential,
                   devices=args.devices,
                   mesh_shape=tuple(args.mesh) if args.mesh else None,
                   wave_impl=args.wave_impl,
                   wave_buckets=not args.no_wave_buckets,
                   horizon=args.horizon, horizon_queue=args.horizon_queue,
                   horizon_timeout_s=args.horizon_timeout_s,
                   server_channel=args.server_channel,
                   sched_timing=args.sched_timing,
                   sched_policy=args.sched_policy, sched_c=args.sched_c,
                   sched_rate_limit=args.sched_rate_limit,
                   sched_stale_cap=args.sched_stale_cap,
                   sched_jitter_sigma=args.sched_jitter_sigma,
                   sched_drop_p=args.sched_drop_p,
                   sched_seed=args.sched_seed,
                   fault_crash_p=args.fault_crash_p,
                   fault_straggler_p=args.fault_straggler_p,
                   fault_corrupt_p=args.fault_corrupt_p,
                   fault_byzantine_p=args.fault_byzantine_p,
                   fault_seed=args.fault_seed,
                   defense=args.defense,
                   defense_norm_cap=args.defense_norm_cap,
                   trace_level=trace_level, trace_dir=args.trace_dir)
    eng = FLEngine(cfg, fn, ds.kind, p0, s0, shards, te.x[:400], te.y[:400])
    log_every = max(args.rounds // 10, 1)
    if args.resume and args.ckpt_dir:
        try:
            start = eng.load_snapshot(args.ckpt_dir)
            print(f"# resumed from snapshot at round {start}")
        except FileNotFoundError:
            pass
    with obs_profile.jax_profile(args.trace_dir, enabled=args.trace_jax):
        if args.ckpt_dir and args.ckpt_every > 0:
            # segmented run: run() stops at each snapshot boundary (the
            # channel is quiescent between aggregations), so a kill at
            # any point loses at most ckpt_every rounds and --resume
            # replays the rest bit-exactly
            res = None
            while eng.t_global < args.rounds:
                upto = min(eng.t_global + args.ckpt_every, args.rounds)
                res = eng.run(upto, log_every=log_every)
                eng.save_snapshot(args.ckpt_dir)
        else:
            res = eng.run(args.rounds, log_every=log_every)
            if args.ckpt_dir:
                eng.save_snapshot(args.ckpt_dir)
    if eng.tracer is not None:
        eng.tracer.close()
        if args.trace_dir:
            obs_export.export_chrome_trace(
                eng.tracer.records,
                os.path.join(args.trace_dir, "trace.json"))
            reg = obs_metrics.from_engine(eng)
            with open(os.path.join(args.trace_dir, "metrics.prom"),
                      "w") as f:
                f.write(reg.to_prometheus())
            with open(os.path.join(args.trace_dir, "metrics.json"),
                      "w") as f:
                json.dump(reg.to_json(), f, indent=1)
            print(f"# trace: {len(eng.tracer.records)} records -> "
                  f"{args.trace_dir}/trace.jsonl (Perfetto: trace.json, "
                  f"metrics: metrics.prom/.json)")
    summary = res.metrics.summary()
    summary["schema"] = SUMMARY_SCHEMA
    # exact byte totals (the *_GB floats above round) — what the trace
    # spans and the CI reconciliation sum against
    summary["tx_bytes"] = int(res.metrics.total_tx_bytes())
    summary["rx_bytes"] = int(res.metrics.total_rx_bytes())
    # scheduling surface: per-client staleness/participation — the
    # device-resident histogram (batched path, one host transfer at run
    # end) plus the scheduler's host accounting
    ss = dict(res.sched_stats)
    ss["staleness_bins"] = [int(v) for v in ss["staleness_bins"]]
    ss["staleness_hist"] = {int(kk): v
                            for kk, v in sorted(res.staleness_hist.items())}
    summary["sched"] = ss
    # hierarchy surface: the server's cross-edge traffic model (unit =
    # one f32 edge partial + its weight scalar; flat mesh = every shard
    # partial crosses, hierarchical = one per edge group)
    summary["traffic"] = dict(eng._server.traffic)
    # typed, schema-versioned summary: numpy scalars become native
    # types and non-string dict keys become strings, so the --json-out
    # file round-trips by equality (asserted below) — no default=str
    summary = obs_export.to_native(summary)
    print(json.dumps(summary, indent=1))
    print(f"# sched[{ss['policy']}/{ss['timing']}] participation "
          f"per client: {ss['participation']}")
    print(f"# rejected uploads: {ss['rejected_uploads']}  "
          f"idle requests: {ss['idle_requests']}  "
          f"no-shows: {ss['no_shows']}  staleness hist: "
          f"{ss['staleness_hist']}")
    print(f"# faults: crashed {ss['crashed_uploads']}  corrupted "
          f"{ss['corrupted_uploads']}  byzantine "
          f"{ss['byzantine_uploads']}  defense[{args.defense}]: "
          f"screened {ss['screened_uploads']}  clipped "
          f"{ss['clipped_uploads']}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(summary, f, indent=1)
        with open(args.json_out) as f:
            assert json.load(f) == summary, \
                "--json-out did not round-trip losslessly"
    if summary["nan_rounds"]:
        # a diverged run must not look like success to the caller
        # (CI, sweep harnesses): name the first poisoned round and
        # exit non-zero
        print(f"# FAILED: non-finite eval from round "
              f"{res.metrics.first_nan_round()} "
              f"({summary['nan_rounds']} nan rounds)")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
