"""The engine's stage spans as the benchmark reads them
(``bench/spans.py``, ``bench/layers/host_ms_per_round.py``,
``bench/layers/state_ms_per_round.py``): on a hand-built trace whose
answers are worked out here, and on a profile recorded on the CPU."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_bench_reducers import MS, built_trace, ctx_for, reader  # noqa: E402
from tinycell import harness  # noqa: E402,F401

import devtrace  # noqa: E402
import run  # noqa: E402
import spans  # noqa: E402

NEW = ("host_ms_per_round", "state_ms_per_round")


def spanned_trace():
    """``built_trace()`` (device ops 0-39, 40-42, 43-45, 46-47 and 48-58 ms
    of each 60 ms round, ``bench.round`` 0-59) with the engine's spans of
    one ``run()`` call per round, in ms from the round's start:

    run 0-58 [ring 0-1, round 1-57 [pop 1-2, wave 2-45 [gather 2-4,
    train 4-39, encode 39-40, fold 40-43, refresh 43-45], finalize 46-47,
    state 47-48, eval 48-57], flush 57-58]

    so the device's idle gaps fall under encode (39-40), fold (42-43),
    the round itself (45-46), state (47-48), no span (58-59) and between
    rounds (59-60)."""
    tr = built_trace()
    stages = [("safl.run", 0, 58, {"rounds": 1}), ("safl.ring", 0, 1, {}),
              ("safl.round", 1, 57, {"uploads": 8, "waves": 1}),
              ("safl.pop", 1, 2, {"popped": 8, "admitted": 8}),
              ("safl.wave", 2, 45, {"wave": 0, "lanes": 8, "bucket": 8}),
              ("safl.gather", 2, 4, {"stacked": 8}),
              ("safl.train", 4, 39, {}), ("safl.encode", 39, 40, {}),
              ("safl.fold", 40, 43, {"folds": 8, "skipped": 0}),
              ("safl.refresh", 43, 45, {"sliced": 0}),
              ("safl.finalize", 46, 47, {}), ("safl.state", 47, 48, {}),
              ("safl.eval", 48, 57, {}), ("safl.flush", 57, 58, {})]
    tr["spans"] = [
        [n, (r + s) * MS, (r + e) * MS, dict(st, round=k + 1)
         if n == "safl.round" else st]
        for k, r in enumerate((0, 60)) for n, s, e, st in stages]
    return tr


def test_readers_on_a_built_trace():
    tr = spanned_trace()
    ctx = ctx_for(tr)
    # device idle under encode, fold, round and state: 1 ms each a round;
    # the 1 ms under no span and the 1 ms between rounds are not the host
    # loop's
    assert reader("host_ms_per_round")(tr, ctx) == pytest.approx(4)
    # gather 2 + refresh 2 + state 1 ms a round
    assert reader("state_ms_per_round")(tr, ctx) == pytest.approx(5)


def test_idle_by_innermost_span():
    tr = spanned_trace()
    idle = spans.idle_by_span(tr, spans.of(tr))
    assert idle == {k: 2 * MS for k in (
        "safl.encode", "safl.fold", "safl.round", "safl.state",
        spans.OUTSIDE, spans.BETWEEN)}
    # every idle ns lands in one bucket
    ctx = ctx_for(tr)
    assert sum(idle.values()) == pytest.approx(
        (ctx["window_s"] - ctx["busy_s"]) * 1e9)
    text = spans.report(tr, spans.of(tr))
    assert "safl.gather" in text and "stacked=8" in text


def test_readers_find_nothing_without_spans_or_rounds():
    tr = spanned_trace()
    ctx = ctx_for(tr)
    for name in NEW:
        assert reader(name)(dict(tr, spans=[]), ctx) is None
        assert reader(name)(tr, dict(ctx, rounds=0)) is None
    # idle needs a device to be idle
    assert reader("host_ms_per_round")(dict(tr, devices=[]), ctx) is None


def _stretch(tr, at, by):
    """``tr`` with every time from ``at`` on put ``by`` ns later, so the
    intervals across ``at`` (round 1's wave, on the device and on the
    host) last ``by`` longer."""
    def t(x):
        return x + by if x >= at else x

    def iv(items):
        return [[n, t(a), t(b), *rest] for n, a, b, *rest in items]
    return dict(tr, window=[t(x) for x in tr["window"]],
                host=iv(tr["host"]), spans=iv(tr["spans"]),
                devices=[dict(d, lines={k: iv(v) for k, v in
                                        d["lines"].items()})
                         for d in tr["devices"]])


def test_host_time_spent_waiting_on_the_device_is_not_counted():
    """A longer wave keeps the host waiting longer inside its spans; the
    device is busy all that time, so ``host_ms_per_round`` holds."""
    tr = spanned_trace()
    slow = _stretch(tr, 30 * MS, 10 * MS)
    assert (spans.total_ns(slow["spans"], "safl.run")
            - spans.total_ns(tr["spans"], "safl.run")) == 10 * MS
    for name in NEW:
        assert reader(name)(slow, ctx_for(slow)) == pytest.approx(
            reader(name)(tr, ctx_for(tr)))


@pytest.fixture(scope="module")
def cpu_profile(tmp_path_factory):
    """A profile recorded on the CPU as ``bench/run.py`` records one: a
    ``bench.window`` holding one ``bench.round`` with engine spans, and
    one engine span after the window."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    d = str(tmp_path_factory.mktemp("bench_trace"))
    jax.profiler.start_trace(d)
    try:
        with TraceAnnotation(devtrace.WINDOW):
            with TraceAnnotation(devtrace.ROUND):
                with TraceAnnotation("safl.run") as sp:
                    with TraceAnnotation("safl.gather") as g:
                        jnp.ones(8).block_until_ready()
                        g.set_metadata(stacked=3)
                    with TraceAnnotation("safl.flush"):
                        pass
                    sp.set_metadata(rounds=1)
        with TraceAnnotation("safl.run"):
            pass
    finally:
        jax.profiler.stop_trace()
    return d


def test_spans_read_from_the_profile_on_disk(cpu_profile, monkeypatch):
    monkeypatch.setattr(run, "TRACE_DIR", cpu_profile)
    tr = devtrace.load(devtrace.find_xplane(cpu_profile))
    assert "spans" not in tr and tr["rounds"] == 1
    got = spans.of(tr)
    assert [s[0] for s in got] == ["safl.run", "safl.gather", "safl.flush"]
    lo, hi = tr["window"]
    assert all(lo <= s < e <= hi for _n, s, e, _st in got)
    assert got[0][3] == {"rounds": 1} and got[1][3] == {"stacked": 3}
    ctx = ctx_for(tr)
    state = reader("state_ms_per_round")(tr, ctx)
    assert state == pytest.approx(spans.total_ns(got, "safl.gather") / 1e6)
    # the CPU profile has no device plane, so no device idle to read
    assert tr["devices"] == []
    assert reader("host_ms_per_round")(tr, ctx) is None
    # a device busy over the window but for the gather waits on the host
    # loop for just the gather's length
    _n, g0, g1, _st = got[1]
    tr = dict(tr, devices=[{"name": "/device:TPU:0", "lines": {
        "XLA Ops": [["%a", lo, g0], ["%b", g1, hi]]}}])
    assert reader("host_ms_per_round")(tr, ctx_for(tr)) == pytest.approx(
        state)


def test_a_profile_of_another_window_is_not_read(cpu_profile, monkeypatch):
    monkeypatch.setattr(run, "TRACE_DIR", cpu_profile)
    tr = devtrace.load(devtrace.find_xplane(cpu_profile))
    lo, hi = tr["window"]
    assert spans.of(dict(tr, window=[lo, hi + 1])) == []
    monkeypatch.setattr(run, "TRACE_DIR", cpu_profile + "-none")
    assert spans.of(tr) == []
    for name in NEW:
        assert reader(name)(tr, ctx_for(tr)) is None
