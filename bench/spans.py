"""The engine's own stage spans in a profiler trace, for the per-layer
readers in ``bench/layers/``.

``FLEngine.run`` writes ``safl.*`` spans (``jax.profiler.TraceAnnotation``,
the table in ``src/repro/obs/README.md``) onto the host plane of the same
``.xplane.pb`` as the device planes, on the same clock.  ``devtrace.load``
keeps only the benchmark's own host spans, so this module reads the
engine's from the file itself:

* ``of(tr)``: the ``safl.*`` spans as ``(name, start_ns, end_ns, stats)``,
  clipped to ``tr["window"]``.  A trace that carries a ``"spans"`` key
  (hand-built, or recorded as JSON) gives that; otherwise the profile
  ``bench/run.py`` wrote under ``run.TRACE_DIR`` is read, once per file,
  and only if its own ``bench.window`` is ``tr["window"]`` (``[]`` if
  there is no such file, or a different one).
* ``idle_by_span(tr, spans)``: the first device's idle time under each
  span name as the innermost engine span, plus ``outside`` (inside a
  ``bench.round``, under no engine span) and ``between rounds``;
  ``engine_idle_ns`` sums the engine's buckets.

    python3 bench/spans.py <trace.xplane.pb>

prints both per round.
"""
from __future__ import annotations

import bisect
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import devtrace  # noqa: E402
import run  # noqa: E402
from layers_common import OPS, events  # noqa: E402

#: the prefix of the engine's stage spans
PREFIX = "safl."
#: idle buckets that are no engine span
OUTSIDE, BETWEEN = "outside", "between rounds"
#: stats that name a span (the others count, and are summed per round)
INDICES = ("round", "wave")


@functools.lru_cache(maxsize=4)
def _read(path: str, _mtime_ns: int) -> tuple:
    """``(window, spans)`` of one ``.xplane.pb``: its ``bench.window``
    interval (None if it has none) and every ``safl.*`` host event."""
    from jax.profiler import ProfileData

    window, spans = None, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == devtrace.WINDOW:
                    window = (int(e.start_ns), int(e.end_ns))
                elif e.name.startswith(PREFIX):
                    spans.append((e.name, int(e.start_ns), int(e.end_ns),
                                  {k: int(v) for k, v in e.stats}))
    spans.sort(key=lambda s: (s[1], -s[2]))
    return window, tuple(spans)


def from_xplane(path: str, window) -> list:
    """The file's ``safl.*`` spans clipped to ``window``; ``[]`` when the
    file's own ``bench.window`` is another."""
    own, spans = _read(path, os.stat(path).st_mtime_ns)
    if own is None or own != tuple(window):
        return []
    lo, hi = own
    return [(n, max(s, lo), min(e, hi), st) for n, s, e, st in spans
            if e > lo and s < hi]


def of(tr: dict) -> list:
    """The engine's spans in ``tr``'s window (the module docstring says
    from where)."""
    if "spans" in tr:
        return [tuple(s) for s in tr["spans"]]
    try:
        path = devtrace.find_xplane(run.TRACE_DIR)
    except FileNotFoundError:
        return []
    return from_xplane(path, tr["window"])


def total_ns(spans, *names) -> int:
    """Summed length of the spans with any of ``names``."""
    return sum(e - s for n, s, e, _st in spans if n in names)


def _innermost(spans) -> list:
    """Cut the timeline into ``(start, end, name)`` pieces, each named
    after the innermost span over it (stage spans nest: one thread, and
    ``with`` blocks)."""
    out, stack, t = [], [], None

    def emit(a, b, name):
        if b > a:
            out.append((a, b, name))

    for n, s, e, _st in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            end, name = stack.pop()
            emit(t, end, name)
            t = end
        if stack:
            emit(t, s, stack[-1][1])
        stack.append((e, n))
        t = s
    while stack:
        end, name = stack.pop()
        emit(t, end, name)
        t = end
    return out


def _overlaps(pieces, ends, g0, g1):
    """``(piece, ns)`` for the sorted, disjoint ``pieces`` (whose ends are
    ``ends``) that overlap ``[g0, g1)``."""
    for i in range(bisect.bisect_right(ends, g0), len(pieces)):
        p = pieces[i]
        if p[0] >= g1:
            break
        yield p, min(p[1], g1) - max(p[0], g0)


def idle_by_span(tr: dict, spans) -> dict:
    """Idle ns of the first device in the window, by the innermost engine
    span over it; ``outside`` and ``between rounds`` hold the rest."""
    lo, hi = tr["window"]
    out = {}
    if not tr["devices"]:
        return out
    busy = sorted((s, e) for _n, s, e in events(tr["devices"][0], OPS))
    gaps, end = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    rounds = sorted((s, e) for n, s, e in tr["host"]
                    if n == devtrace.ROUND)
    r_ends = [e for _s, e in rounds]
    pieces = _innermost(spans)
    p_ends = [e for _s, e, _n in pieces]

    def add(key, ns):
        if ns > 0:
            out[key] = out.get(key, 0) + ns

    for g0, g1 in gaps:
        in_round = sum(ns for _r, ns in _overlaps(rounds, r_ends, g0, g1))
        add(BETWEEN, (g1 - g0) - in_round)
        named = 0
        for p, ns in _overlaps(pieces, p_ends, g0, g1):
            add(p[2], ns)
            named += ns
        add(OUTSIDE, in_round - named)
    return out


def engine_idle_ns(tr: dict, spans) -> int:
    """The first device's idle ns in the window under any engine span."""
    return sum(ns for k, ns in idle_by_span(tr, spans).items()
               if k not in (OUTSIDE, BETWEEN))


def report(tr: dict, spans) -> str:
    """Per traced round: each span name's count, time and counting
    stats, and the device's idle time by innermost span."""
    n = max(tr["rounds"], 1)
    out = [f"window {(tr['window'][1] - tr['window'][0]) / 1e9:.4f}s "
           f"rounds {tr['rounds']} (per round below)",
           f"{'span':16s} {'count':>8s} {'ms':>10s}  stats"]
    names = sorted({s[0] for s in spans})
    for name in names:
        mine = [s for s in spans if s[0] == name]
        stats = {}
        for *_x, st in mine:
            for k, v in st.items():
                if k not in INDICES:
                    stats[k] = stats.get(k, 0) + v
        out.append(f"{name:16s} {len(mine) / n:8.2f} "
                   f"{total_ns(mine, name) / 1e6 / n:10.3f}  "
                   + " ".join(f"{k}={v / n:g}" for k, v in sorted(
                       stats.items())))
    idle = idle_by_span(tr, spans)
    tot = sum(idle.values()) or 1
    out.append(f"device idle by innermost span: "
               f"{sum(idle.values()) / 1e6 / n:.3f} ms")
    for k in sorted(idle, key=idle.get, reverse=True):
        out.append(f"  {k:16s} {idle[k] / 1e6 / n:10.3f} ms "
                   f"{100 * idle[k] / tot:6.2f}%")
    return "\n".join(out)


if __name__ == "__main__":
    trace = devtrace.load(sys.argv[1])
    print(report(trace, from_xplane(sys.argv[1], trace["window"])))
