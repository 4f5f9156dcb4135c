"""Sets a cell's limits of the correctness comparison from the readings
``bench/control.py`` wrote, by one fixed rule, and writes
``bench/limits/<cell>.json``.

    python3 bench/setlimits.py --workload <cell> readings.jsonl ...

For each compared number (``bench/check.py``):

* lower reading: the largest the program reads over the seeds;
* upper reading: the smallest that a variant which must fail reads over
  the seeds, among the variants that read far enough above the lower
  reading to count: the bfloat16 control, a state left unchanged and a
  wire fault that keeps no residual or zeroes one at three times the
  lower reading or more (the last two read 1, the most a gap can, by
  construction and not by noise), every other planted fault
  (``no_adopt`` included) at ten times or more;
* limit: ``lower^0.4 x upper^0.6``, rounded down to two digits, so there
  is more room above the lower reading than below the upper one; null
  (printed, not compared) where no variant counts.

It then judges every variant of every seed by those limits and prints
which fail; the control and every planted fault of ``MUST_FAIL`` have
to fail on every seed, and the program has to pass; the exit code says
whether they do.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check  # noqa: E402
import harness  # noqa: E402

#: variants that must fail, and the least multiple of the lower reading
#: at which each sets an upper reading
MUST_FAIL = {"control": 3.0, "unchanged": 3.0, "drop_half": 10.0,
             "alter_one": 10.0, "no_ef": 3.0, "f32_wire": 3.0,
             "reset_residual": 3.0}
#: planted faults that set an upper reading the same way, read and
#: reported, but not required to fail: a client that never adopts the
#: global model moves the checked rounds by little, and a residual kept
#: but never added back changes neither what a client holds nor the
#: global weights by more than rounding (PERF.md)
ALSO_READ = {"no_adopt": 10.0, "no_feedback": 10.0}


def round_down(x: float) -> float:
    """``x`` rounded down to two significant digits."""
    e = math.floor(math.log10(x)) - 1
    return round(math.floor(x / 10 ** e) * 10 ** e, 12)


def derive(lines: list) -> dict:
    """Lower and upper readings and the limit of every number.  A line
    written before a number existed does not count for it."""
    lower, upper, limits = {}, {}, {}
    for n in check.NUMBERS:
        progs = [ln["program"][n] for ln in lines
                 if n in ln.get("program", {})]
        if not progs:  # never read: not compared
            lower[n] = upper[n] = limits[n] = None
            continue
        lower[n] = max(progs)
        cands = {}
        for v, mult in {**MUST_FAIL, **ALSO_READ}.items():
            vals = [ln[v][n] for ln in lines if n in ln.get(v, {})]
            if vals and min(vals) >= mult * lower[n]:
                cands[v] = min(vals)
        if cands:
            src = min(cands, key=cands.get)
            upper[n] = {"value": cands[src], "from": src}
            limits[n] = round_down(lower[n] ** 0.4 * cands[src] ** 0.6)
        else:
            upper[n] = None
            limits[n] = None
    return dict(limits=limits, lower=lower, upper=upper)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("readings", nargs="+")
    args = ap.parse_args(argv)
    lines = []
    for path in args.readings:
        with open(path) as f:
            lines += [json.loads(t) for t in f if t.strip()]
    d = derive(lines)
    n_prog = sum("program" in ln for ln in lines)
    n_var = {v: sum(v in ln for ln in lines)
             for v in {**MUST_FAIL, **ALSO_READ}}
    out = {
        "limits": d["limits"],
        "readings": {n: {"lower": d["lower"][n], "upper": d["upper"][n]}
                     for n in check.NUMBERS},
        "seeds": {"program": n_prog, **n_var},
        "rule": "limit = lower^0.4 x upper^0.6, rounded down to two digits;"
                " upper = least reading of the bf16 control, of a state"
                " left unchanged or of a wire fault that keeps no residual"
                " or zeroes one at >= 3x the lower reading, or of another"
                " planted fault at >= 10x",
    }
    path = os.path.join(harness.BENCH, "limits", args.workload + ".json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(json.dumps(out["readings"]))
    print(f"limits {json.dumps(d['limits'])}")
    ok = True
    for ln in lines:
        for v in ("program", *MUST_FAIL, *ALSO_READ):
            if v not in ln:
                continue
            # judge a line by the numbers it holds
            held = {"limits": {n: (lim if n in ln[v] else None)
                               for n, lim in out["limits"].items()}}
            passed, rows = check.verdict(
                {n: 0.0 for n in check.NUMBERS} | ln[v], held)
            caught = [r[0] for r in rows if r[2] is not None
                      and not (r[1] <= r[2] if r[0] != "adopted"
                               else r[1] >= r[2])]
            if v == "program":
                good = passed
            elif v in MUST_FAIL:
                good = not passed
            else:  # read, not required to fail
                good = True
            ok = ok and good
            print(f"seed {ln['seed']} {v}: {'pass' if passed else 'fail'}"
                  f" {caught}{'' if good else '  <- WRONG'}")
    print("every variant judged as it must be" if ok
          else "some variant judged wrongly")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
