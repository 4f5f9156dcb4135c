"""Chip benchmark of the SAFL engine (``FLEngine`` on the batched
semi-asynchronous path, streaming server channel).

    python3 bench/run.py --workload <cell> --seed 7 --seconds 30 --trace 0

The window runs the loop of a researcher who reads every round's accuracy
as it lands: one aggregation round per ``FLEngine.run`` call, each timed
from the call to its return (the round's metrics are then on the host).

Set-up (``setup_s``, from process start to the first timed round):
inputs and weights made on the device from ``--seed``, the engine built,
the first ``checked_rounds`` rounds driven and recorded for the
correctness comparison, then warm-up rounds until no program compiles.
After the window the engine is freed and the plain reference
(``bench/reference.py``) replays the checked rounds; ``bench/check.py``
compares them (``correct``).

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces
the window with the JAX profiler and prints the cell's per-layer metrics,
each read by ``bench/layers/<metric>.py``.  The last line of standard
output is one JSON object; the numbers compared, with their limits, are
the last lines of standard error and the last key of that object.

Off the chip, or with a server-backend override in the environment, the
command exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

#: where a traced run writes its profile (inside the checkout, fixed)
TRACE_DIR = os.path.join(harness.ROOT, ".bench_trace")
GIB = float(1 << 30)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def fail(msg: str) -> int:
    log(f"bench: {msg}")
    return 2


def device_info(devs, n_used: int) -> dict:
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:n_used])
    d0 = devs[0]
    return dict(platform=d0.platform, kind=d0.device_kind, count=len(devs),
                memory_peak_bytes=peak)


def per_layer_metrics(bench: dict, cell_name: str) -> list:
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])]


def read_layers(bench, cell, tr, ctx) -> dict:
    out = {}
    for m in per_layer_metrics(bench, cell["name"]):
        mod = harness.load_module(
            os.path.join(harness.BENCH, "layers", m["name"] + ".py"),
            "bench_layer_" + m["name"])
        val = mod.read(tr, ctx)
        if val is None:
            log(f"layer {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for var in harness.OVERRIDES:
        if var in os.environ:
            return fail(f"{var} is set; the benchmark runs the backend the "
                        "chip selects")
    if not os.path.isdir(os.path.join(harness.SRC, "repro")):
        return fail(f"the program is not in this checkout ({harness.SRC})")
    bench = harness.benchmark()
    try:
        cell = harness.find_cell(args.workload, bench)
    except KeyError as e:
        return fail(str(e))
    peaks = harness.read_json(os.path.join(harness.BENCH, "peaks.json"))

    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        return fail("JAX finds no accelerator")
    if len(devs) < cell["chips"]:
        return fail(f"the cell asks for {cell['chips']} chips, JAX sees "
                    f"{len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks["devices"]:
        return fail(f"device kind {kind!r} is not in bench/peaks.json")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", harness.CACHE_DIR)
    return measure(args, bench, cell, peaks["devices"][kind])


def measure(args, bench: dict, cell: dict, peak: dict) -> int:
    """Set-up, window, metrics and comparison of one run on the devices
    JAX has (``main`` has checked they are the chips the cell asks for)."""
    import jax

    devs = jax.devices()
    counter = harness.compile_counter()

    # ---- set-up: inputs, engine, checked rounds, warm-up ----
    cfg, traffic = cell["cfg"], cell["traffic"]
    stages = [("start", T_START), ("jax", time.perf_counter())]
    data = harness.make_data(cfg, traffic, args.seed)
    weights = harness.make_weights(cell, args.seed)
    p0 = jax.device_get(weights[0])
    stages.append(("inputs", time.perf_counter()))
    eng = harness.build_engine(cell, args.seed, data, weights)
    del weights
    stages.append(("engine", time.perf_counter()))
    n_checked = int(traffic["checked_rounds"])
    prog = harness.checked_rounds(eng, n_checked, p0)
    stages.append(("checked", time.perf_counter()))
    harness.prewarm_ring(int(traffic["max_rounds"]))
    quiet, warm = 0, 0
    while quiet < 3:
        before = len(counter)
        harness.run_round(eng)
        warm += 1
        quiet = quiet + 1 if len(counter) == before else 0
        if warm > 50:
            return fail(f"programs still compile after {warm} warm-up "
                        f"rounds: {counter.names[before:]}")
    stages.append(("warm-up", time.perf_counter()))
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f}s: {n_checked} checked + {warm} warm-up "
        f"rounds, {len(counter)} programs compiled or loaded")
    log("set-up stages: " + ", ".join(
        f"{name} {t - stages[i][1]:.3f}s"
        for i, (name, t) in enumerate(stages[1:])))

    # ---- the measured window ----
    compiled_before = len(counter)
    uploads_before = int(eng.sched.participation.sum())
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    times, failed = [], 0
    t_win = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() - t_win < args.seconds:
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench.round"):
                    res = harness.run_round(eng)
                rec = res.metrics.records[-1]
                ok = (rec.round == eng.t_global
                      and math.isfinite(rec.loss)
                      and math.isfinite(rec.accuracy))
            except Exception as e:  # a round that raises is a failed round
                log(f"round failed: {e!r}")
                ok = False
            times.append(time.perf_counter() - t0)
            failed += not ok
            if not ok and len(times) > 3 and failed == len(times):
                break
    window_s = time.perf_counter() - t_win
    if args.trace:
        jax.profiler.stop_trace()
    uploads = int(eng.sched.participation.sum()) - uploads_before
    in_window = counter.names[compiled_before:]
    log(f"window {window_s:.3f}s: {len(times)} rounds, {uploads} uploads, "
        f"{failed} failed, {len(in_window)} programs compiled inside the "
        f"window {in_window}")
    dev = device_info(devs, cell["chips"])

    metrics = {}
    breakdown = None
    if args.trace:
        import layers_common
        import devtrace as tracemod

        tr = tracemod.load(tracemod.find_xplane(TRACE_DIR))
        ctx = layers_common.context(cell, tr, peak)
        metrics = read_layers(bench, cell, tr, ctx)
        dev["busy_s"] = ctx["busy_s"]
        dev["window_s"] = ctx["window_s"]
        breakdown = layers_common.breakdown(tr, ctx)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        metrics = {
            "uploads_per_s": {"value": uploads / window_s,
                              "unit": "uploads/s"},
            "round_p90_ms": {"value": 1e3 * statistics.quantiles(
                times, n=10, method="inclusive")[8], "unit": "ms"},
            "peak_hbm_gib": {"value": dev["memory_peak_bytes"] / GIB,
                             "unit": "GiB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }

    # ---- the comparison, once the program's state is freed ----
    del eng
    res = None
    gc.collect()
    t_ref = time.perf_counter()
    import check
    import reference

    ref = reference.run(cfg, cell["ref"], traffic,
                        harness.population(traffic, args.seed), data,
                        harness.make_weights(cell, args.seed), n_checked)
    read = check.readings(prog, ref)
    correct, rows = check.verdict(read, cell["limits"])
    correct = correct and failed == 0 and len(times) > 0
    log(f"reference {time.perf_counter() - t_ref:.3f}s over {n_checked} "
        f"rounds, {ref['uploads']} uploads, {read['reuploads']} from "
        f"clients that uploaded before, {read['adopted']} trained from an "
        f"adopted global model; worst leaves: grad {read['grad_leaf']}, "
        f"last {read['last_leaf']}, change {read['change_leaf']}; "
        f"{read['left_out']} leaves left out")
    for name, val, lim in rows:
        log(f"compared {name} {val!r} limit {lim!r}")
    out = dict(correct=correct, attempted=len(times), failed=failed,
               metrics=metrics, device=dev)
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {name: {"value": val, "limit": lim}
                       for name, val, lim in rows}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
