"""Run ``bench/run.py`` with a log of every round and of the interpreter's
full (generation 2) collections, and, on a traced run, the engine's span
report; then summarize such logs.  A diagnostic beside the benchmark: the
run itself, its result line and its metrics are ``run.py``'s, unchanged.

    python3 bench/roundlog.py --log OUT [--spans SPANS] -- --workload ... --seed ... --seconds 50 --trace 0|1
    python3 bench/roundlog.py --summary OUT [OUT ...]

The log (JSON) holds ``rounds``, each ``[start_s, seconds]`` on the
``time.perf_counter`` clock (checked, warm-up and window rounds alike),
``gc2``, each full collection as ``[start_s, seconds]``, and
``window_rounds``, the number of rounds the window attempted (the last
ones of ``rounds``).  ``--spans`` writes ``spans.report`` of the traced
window before ``run.py`` deletes its trace.  The summary prints, per log,
the window's median round, the rounds that took over ``STALL`` times it,
and the full collections that fell inside the window and in the whole
run.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: a window round that takes this many times the median is a stall
STALL = 3.0


def record(log_path: str, spans_path: str, argv: list) -> int:
    import harness
    import run

    rounds, gc2, started = [], [], []
    inner = harness.run_round

    def timed(eng):
        t = time.perf_counter()
        try:
            return inner(eng)
        finally:
            rounds.append([t, time.perf_counter() - t])

    def on_gc(phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            started.append(time.perf_counter())
        elif started:
            t = started.pop()
            gc2.append([t, time.perf_counter() - t])

    read_layers = run.read_layers

    def read_and_report(bench, cell, tr, ctx):
        import spans

        with open(spans_path, "w") as f:
            f.write(spans.report(tr, spans.of(tr)) + "\n")
        return read_layers(bench, cell, tr, ctx)

    harness.run_round = timed
    if spans_path:
        run.read_layers = read_and_report
    gc.callbacks.append(on_gc)
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = run.main(argv)
    finally:
        gc.callbacks.remove(on_gc)
        harness.run_round, run.read_layers = inner, read_layers
    sys.stdout.write(out.getvalue())
    last = out.getvalue().strip().splitlines()
    n_window = json.loads(last[-1])["attempted"] if rc == 0 and last else 0
    with open(log_path, "w") as f:
        json.dump(dict(rounds=rounds, gc2=gc2, window_rounds=n_window), f)
    return rc


def summary(log: dict) -> dict:
    """The window's median round (ms), its stalls (s) and the full
    collections (count, s) inside the window and in the whole run."""
    window = log["rounds"][len(log["rounds"]) - log["window_rounds"]:]
    if not window:
        return {}
    secs = [d for _t, d in window]
    med = statistics.median(secs)
    lo, hi = window[0][0], window[-1][0] + window[-1][1]
    inside = [d for t, d in log["gc2"] if lo <= t < hi]
    return dict(rounds=len(window), median_ms=1e3 * med,
                stalls_s=[d for d in secs if d > STALL * med],
                gc2_in_window=(len(inside), sum(inside)),
                gc2_in_run=(len(log["gc2"]), sum(d for _t, d in log["gc2"])))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    rest = []
    if "--" in argv:
        i = argv.index("--")
        argv, rest = argv[:i], argv[i + 1:]
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--log")
    ap.add_argument("--spans", default="")
    ap.add_argument("--summary", nargs="+", default=[])
    args = ap.parse_args(argv)
    for path in args.summary:
        with open(path) as f:
            print(path, json.dumps(summary(json.load(f))))
    if args.log:
        return record(args.log, args.spans, rest)
    return 0


if __name__ == "__main__":
    sys.exit(main())
