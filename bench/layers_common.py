"""Trace arithmetic the per-layer readers share: which device line holds
the operations and which the programs, interval unions, and the context
every reader gets.

On a TPU plane the profiler writes one event per executed XLA program on
the ``XLA Modules`` line (named ``jit_<function>(<id>)``) and one event
per operation on the ``XLA Ops`` line; a Pallas kernel is an operation
named after its kernel.
"""
from __future__ import annotations

import bisect
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import work  # noqa: E402,F401

OPS, MODULES = "XLA Ops", "XLA Modules"


def events(dev: dict, line: str) -> list:
    return dev["lines"].get(line, [])


def module_name(event_name: str) -> str:
    """``jit_round_fn(1234)`` -> ``jit_round_fn``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def union_ns(intervals) -> int:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def used_devices(tr: dict, chips: int) -> list:
    return tr["devices"][:chips]


def module_time_ns(dev: dict, pattern: str) -> int:
    """Device time of the programs whose module name matches ``pattern``
    (a regular expression, matched against the whole name)."""
    rx = re.compile(pattern)
    return sum(e - s for n, s, e in events(dev, MODULES)
               if rx.fullmatch(module_name(n)))


def context(cell: dict, tr: dict, peak: dict) -> dict:
    """What every reader gets besides the trace: the traced window and
    round count, the peaks of this device kind, and the work of a round
    from shapes."""
    cfg, traffic = cell["cfg"], cell["traffic"]
    lo, hi = tr["window"]
    devs = used_devices(tr, cell["chips"])
    busy = [union_ns((s, e) for _n, s, e in events(d, OPS)) for d in devs]
    eng = traffic["engine"]
    return dict(
        chips=cell["chips"], rounds=tr["rounds"], window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / len(busy) / 1e9 if busy else 0.0, peak=peak,
        round_flops=work.round_flops(cfg, cell["ref"], traffic),
        uploads_per_round=int(eng["k"]),
        fold_bytes=work.fold_bytes(work.row_length(cfg, cell["ref"]),
                                   eng.get("wire", "f32"),
                                   int(eng.get("quant_block", 512))))


def breakdown(tr: dict, ctx: dict, top: int = 10) -> dict:
    """The device operations that took most time on the first device, and
    the longest idle gaps there, summed by name: the program the gap lies
    inside, or the programs on either side of it, and whether the host was
    inside a round (``bench.round``) or between rounds."""
    if not tr["devices"]:
        return None
    dev = tr["devices"][0]
    tot = {}
    for n, s, e in events(dev, OPS):
        tot[n] = tot.get(n, 0) + (e - s)
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    mods = sorted(events(dev, MODULES), key=lambda ev: ev[1])
    busy = sorted((s, e) for _n, s, e in events(dev, OPS))
    gaps, end = [], tr["window"][0]
    for s, e in busy + [(tr["window"][1], tr["window"][1])]:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    starts = [s for _n, s, _e in mods]
    ends = [e for _n, _s, e in mods]
    rounds = sorted((s, e) for n, s, e in tr["host"] if n == "bench.round")
    r_starts = [s for s, _e in rounds]
    named = {}
    for g0, g1 in gaps:
        r = bisect.bisect_right(r_starts, g0) - 1
        where = ("in round" if r >= 0 and g1 <= rounds[r][1]
                 else "between rounds")
        k = bisect.bisect_right(starts, g0) - 1
        if k >= 0 and g1 <= ends[k]:  # a program's own gap between ops
            key = f"{where}: inside {module_name(mods[k][0])}"
        else:
            i = bisect.bisect_right(ends, g0) - 1
            j = bisect.bisect_left(starts, g1)
            before = module_name(mods[i][0]) if i >= 0 else "start"
            after = module_name(mods[j][0]) if j < len(mods) else "end"
            key = f"{where}: {before} -> {after}"
        named[key] = named.get(key, 0) + (g1 - g0)
    idle = sorted(named.items(), key=lambda kv: -kv[1])[:top]
    return dict(device_ops=[[n, v / 1e9] for n, v in ops],
                idle_gaps=[[n, v / 1e9] for n, v in idle])
