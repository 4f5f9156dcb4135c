"""Reading a profiler trace into plain event lists for the per-layer
readers in ``bench/layers/``.

``load(path)`` turns one ``.xplane.pb`` into a dict:

* ``window``: ``(start_ns, end_ns)`` of the benchmark's own
  ``bench.window`` span, on the trace's clock;
* ``rounds``: the number of ``bench.round`` spans inside it;
* ``host``: the benchmark's own spans, ``(name, start_ns, end_ns)``;
* ``devices``: one entry per accelerator plane (``/device:TPU:<n>``),
  ``{"name", "lines": {line name: [(event name, start_ns, end_ns),
  ...]}}``, holding the ``XLA Modules`` line (one event per executed
  program, ``jit_<function>(<id>)``) and the ``XLA Ops`` line (one event
  per operation, named ``<op> <opcode>``, e.g. ``%_fold.1
  custom-call``: the profiler's full HLO text is cut to that), and only
  events that overlap the window, clipped to it.

The result is plain JSON, so a small recorded trace can be kept as a
test fixture and read back with ``from_json``:

    python3 bench/devtrace.py <trace.xplane.pb> <out.json.gz>

writes it, and prints a summary of it.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import sys

#: the benchmark's own spans (jax.profiler.TraceAnnotation names)
WINDOW, ROUND = "bench.window", "bench.round"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    return found[-1]


#: the lines kept from each device plane
LINES = ("XLA Modules", "XLA Ops")
_DEVICE = re.compile(r"/device:(TPU|GPU):\d+")
#: "%fusion.3 = f32[8]{0} fusion(...)" -> ("%fusion.3", "fusion")
_HLO = re.compile(r"(%?[\w.\-]+) = .*?\s([a-z][\w\-]*)\(")


def _is_device(plane_name: str) -> bool:
    return bool(_DEVICE.fullmatch(plane_name))


def op_name(text: str) -> str:
    """The profiler names an operation by its whole HLO instruction; keep
    the instruction's name and opcode."""
    m = _HLO.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, window = [], None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in (WINDOW, ROUND):
                    host.append((e.name, int(e.start_ns), int(e.end_ns)))
                    if e.name == WINDOW:
                        window = (int(e.start_ns), int(e.end_ns))
    if window is None:
        raise ValueError(f"no {WINDOW} span in {path}")
    lo, hi = window
    devices = []
    for plane in pd.planes:
        if not _is_device(plane.name):
            continue
        lines = {}
        for line in plane.lines:
            if line.name not in LINES:
                continue
            short = op_name if line.name == "XLA Ops" else str
            evs = []
            for e in line.events:
                s, t = int(e.start_ns), int(e.end_ns)
                if t <= lo or s >= hi:
                    continue
                evs.append((short(e.name), max(s, lo), min(t, hi)))
            if evs:
                lines[line.name] = evs
        devices.append({"name": plane.name, "lines": lines})
    devices.sort(key=lambda d: d["name"])
    host = [h for h in host if h[0] == WINDOW or lo <= h[1] < hi]
    rounds = sum(1 for h in host if h[0] == ROUND)
    return dict(window=list(window), rounds=rounds, host=host,
                devices=devices)


def to_json(tr: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(tr, f)


def from_json(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def summary(tr: dict, top: int = 40) -> str:
    """A human-readable look at a trace: lines per device plane, and the
    event names that take most time on each."""
    out = [f"window {(tr['window'][1] - tr['window'][0]) / 1e9:.4f}s "
           f"rounds {tr['rounds']}"]
    for d in tr["devices"]:
        out.append(f"plane {d['name']}")
        for lname, evs in d["lines"].items():
            tot = {}
            cnt = {}
            for n, s, t in evs:
                tot[n] = tot.get(n, 0) + (t - s)
                cnt[n] = cnt.get(n, 0) + 1
            out.append(f"  line {lname!r}: {len(evs)} events, "
                       f"{sum(tot.values()) / 1e9:.4f}s")
            for n in sorted(tot, key=tot.get, reverse=True)[:top]:
                out.append(f"    {tot[n] / 1e6:10.3f} ms x{cnt[n]:6d}  {n}")
    return "\n".join(out)


if __name__ == "__main__":
    trace = load(sys.argv[1])
    to_json(trace, sys.argv[2])
    print(summary(trace))
