"""Observability layer (PR 10 tentpole): span tracer parity, Chrome-trace
export, engine reconciliation, metrics registry, profiling hooks, and the
DeviceMetricsRing edge cases the tracer leans on.

The invariants pinned here:

  * tracing off is bit-exact with the pre-PR engine (no tracer object is
    even constructed), and tracing on changes no device code — the traced
    batched run matches the untraced one bitwise;
  * the sequential and horizon-batched paths emit IDENTICAL span streams
    (the parity-by-sorted-flush discipline), wall-clock stripped;
  * spans reconcile exactly with the engine's own accounting: ingest
    bytes sum to tx_bytes, the staleness multiset matches the run's
    histogram, fac==0 ingests count the screened uploads;
  * the Chrome-trace export validates against the Trace Event Format;
  * the ring's growth/sentinel/single-transfer contracts hold.
"""
import json

import jax
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core import FLEngine
from repro.core.metrics import DeviceMetricsRing
from repro.data import build_client_shards, make_dataset, train_test_split
from repro.models.vision_cnn import build_paper_model
from repro.obs import export as obs_export
from repro.obs import report as obs_report
from repro.obs.metrics import Counter, MetricsRegistry, from_engine
from repro.obs.profile import (CompileLog, TransferScope, cache_size,
                               engine_compile_log)
from repro.obs.trace import SpanTracer, canonical


@pytest.fixture(scope="module")
def setup():
    ds = make_dataset("cifar10", n=240, seed=0, hw=16)
    tr, te = train_test_split(ds)
    shards = build_client_shards(tr, "iid", n_clients=6, batch_size=16)
    p0, s0, apply_fn = build_paper_model("cnn", jax.random.PRNGKey(0),
                                         width=4, image_size=16)
    return shards, te, p0, s0, apply_fn


def _run(setup, rounds=4, n_clients=6, k=3, **kw):
    shards, te, p0, s0, apply_fn = setup
    cfg = FLConfig(n_clients=n_clients, k=k, mode="semi_async",
                   aggregation=kw.pop("aggregation", "fedbuff"),
                   client_lr=0.05, server_lr=0.05, target_accuracy=0.3,
                   **kw)
    eng = FLEngine(cfg, apply_fn, "image", p0, s0, shards,
                   te.x[:100], te.y[:100])
    return eng.run(rounds), eng


@pytest.fixture(scope="module")
def traced_pair(setup):
    """The same traced experiment on both engine paths."""
    rb, eb = _run(setup, trace_level="upload")
    rs, es = _run(setup, trace_level="upload", batch_clients=False)
    return rb, eb, rs, es


def _ingests(eng):
    return [r for r in eng.tracer.records if r.get("name") == "ingest"]


def _rounds(eng):
    return [r for r in eng.tracer.records if r.get("name") == "round"]


# ------------------------- span-stream parity -------------------------


def test_seq_batched_span_parity(traced_pair):
    """Both engine paths emit the SAME span stream (wall-clock stripped):
    the horizon-buffered sorted flush makes record order deterministic,
    and every per-slot value (staleness, bytes, fac, weight) is computed
    identically — extending the seq-vs-batched parity oracle to traces."""
    _, eb, _, es = traced_pair
    cb, cs = canonical(eb.tracer.records), canonical(es.tracer.records)
    assert len(cb) > 10
    assert cb == cs
    # the volatile key really was the only difference
    assert all("wall" in r for r in _rounds(eb))


def test_tracing_on_is_bit_exact_with_off(setup, traced_pair):
    """Tracing is pure host bookkeeping: the traced run's trained model
    and accounting match the untraced run bit for bit."""
    rb, eb, _, _ = traced_pair
    ru, eu = _run(setup)
    assert eu.tracer is None  # off => no tracer object at all
    np.testing.assert_array_equal(np.asarray(eb._flat_params),
                                  np.asarray(eu._flat_params))
    assert rb.staleness_hist == ru.staleness_hist
    assert rb.metrics.total_tx_bytes() == ru.metrics.total_tx_bytes()
    assert rb.metrics.total_rx_bytes() == ru.metrics.total_rx_bytes()


# --------------------- engine <-> span reconciliation ---------------------


def test_spans_reconcile_with_engine_accounting(traced_pair):
    _, eb, _, _ = traced_pair
    ingests = _ingests(eb)
    assert sum(i["bytes"] for i in ingests) == eb.tx_bytes
    hist = {}
    for i in ingests:
        if "round" in i:  # tail-flushed pending uploads never aggregated
            hist[i["staleness"]] = hist.get(i["staleness"], 0) + 1
    assert hist == {int(s): int(n)
                    for s, n in eb.staleness_hist.items() if n}
    # the last round span's cumulative counters are the engine's
    counts = _rounds(eb)[-1]["counts"]
    assert counts["tx_bytes"] == eb.tx_bytes
    assert counts["rx_bytes"] == eb.rx_bytes
    assert counts["screened"] == eb.screened_uploads
    # per-round K matches the ingest count of that horizon
    for rs in _rounds(eb):
        rnd = rs["round"]
        assert rs["k"] == sum(1 for i in ingests if i.get("round") == rnd)


def test_span_timing_is_wellformed(traced_pair):
    """train -> wire -> ingest chain per upload: contiguous on the
    simulated clock (arrival = wake + compute + comm), inside the round
    window; every span has t0 <= t1."""
    _, eb, _, _ = traced_pair
    recs = eb.tracer.records
    spans = [r for r in recs if r.get("kind") == "span"]
    assert all(r["t0"] <= r["t1"] for r in spans)
    by_key = {}
    for r in spans:
        if r["name"] in ("train", "wire"):
            by_key[(r["name"], r["cid"], r["slot"], r.get("round"))] = r
    rounds = {r["round"]: r for r in _rounds(eb)}
    for i in _ingests(eb):
        key = (i["cid"], i["slot"], i.get("round"))
        train, wire = by_key[("train",) + key], by_key[("wire",) + key]
        assert train["t1"] == wire["t0"]
        assert wire["t1"] == i["t"]
        if i.get("round") in rounds:
            assert i["t"] <= rounds[i["round"]]["t1"]
    for rs in rounds.values():
        agg = [r for r in spans if r["name"] == "aggregate"
               and r.get("round") == rs["round"]]
        assert len(agg) == 1 and agg[0]["t1"] == rs["t1"]


def test_defense_verdicts_reconcile(setup):
    """fac carried on ingest records: fac == 0 is a screened upload, and
    the count matches the engine's defense accounting exactly."""
    _, eng = _run(setup, aggregation="fedsgd", wire="q8",
                  trace_level="upload", defense="screen",
                  fault_corrupt_p=0.3)
    assert eng.screened_uploads > 0, "fixture screened nothing; tune p"
    screened = sum(1 for i in _ingests(eng) if i.get("fac") == 0.0)
    assert screened == eng.screened_uploads
    counts = _rounds(eng)[-1]["counts"]
    assert counts["screened"] == eng.screened_uploads
    assert counts["corrupted"] == eng.corrupted_uploads


def test_round_level_tracing_drops_upload_spans(setup):
    _, eng = _run(setup, trace_level="round")
    names = {r.get("name") for r in eng.tracer.records}
    assert "ingest" not in names and "train" not in names
    assert len(_rounds(eng)) == 4  # one round span per horizon


def test_trace_level_validated(setup):
    with pytest.raises(AssertionError):
        FLConfig(trace_level="verbose").validate()
    with pytest.raises(ValueError):
        SpanTracer(level="off")


# --------------------- JSONL + Chrome-trace export ---------------------


def test_jsonl_roundtrip_and_report(setup, tmp_path, capsys):
    _, eng = _run(setup, trace_level="upload", trace_dir=str(tmp_path))
    eng.tracer.close()
    records = obs_export.load_jsonl(eng.tracer.path)
    assert records == eng.tracer.records  # JSONL is lossless
    text = obs_report.render(records)
    assert text.count("\nr") >= 4  # one timeline line per round
    assert "staleness at ingest:" in text and "totals:" in text
    assert obs_report.main([eng.tracer.path]) == 0
    assert "bytes by wire:" in capsys.readouterr().out


def test_chrome_trace_export_validates(traced_pair, tmp_path):
    _, eb, _, _ = traced_pair
    out = str(tmp_path / "trace.json")
    obj = obs_export.export_chrome_trace(eb.tracer.records, out)
    with open(out) as f:
        assert json.load(f) == obj  # file round-trips
    n = obs_export.validate_chrome_trace(obj)
    assert n == len(obj["traceEvents"]) > 0
    evs = obj["traceEvents"]
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "server" in names
    assert any(t.startswith("client ") for t in names)
    # queue depth counter rises on ingest and resets at each aggregate
    qd = [e["args"]["uploads"] for e in evs
          if e["ph"] == "C" and e["name"] == "queue_depth"]
    assert max(qd) >= 3 and 0 in qd
    assert obj["otherData"]["schema"] == 1


def test_chrome_trace_validation_rejects_bad_shapes():
    with pytest.raises(ValueError):
        obs_export.validate_chrome_trace({"traceEvents": []})
    with pytest.raises(ValueError):
        obs_export.validate_chrome_trace(
            {"traceEvents": [{"ph": "Z", "name": "x", "pid": 1}]})
    with pytest.raises(ValueError):
        obs_export.validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "name": "x", "pid": 1,
                              "ts": 0.0, "dur": -1.0, "tid": 0}]})


def test_to_native_json_roundtrip():
    obj = {"a": np.float32(1.5), "b": np.int64(3),
           "c": np.arange(3, dtype=np.int32), 4: "int-key",
           "d": {"nested": np.bool_(True)}, "e": [np.float64(0.25), None]}
    native = obs_export.to_native(obj)
    assert json.loads(json.dumps(native)) == native
    assert native["4"] == "int-key" and native["b"] == 3
    assert native["c"] == [0, 1, 2]


# ------------------------- metrics registry -------------------------


def test_registry_exposition():
    reg = MetricsRegistry()
    c = reg.counter("up_total", "uploads", wire="q8")
    c.inc(3)
    assert reg.counter("up_total", wire="q8") is c  # get-or-create
    reg.gauge("depth").set(2.5)
    h = reg.histogram("stale", buckets=(1, 2))
    h.observe(0.5)
    h.observe(5)
    text = reg.to_prometheus()
    assert "# HELP up_total uploads" in text
    assert "# TYPE up_total counter" in text
    assert 'up_total{wire="q8"} 3' in text
    assert "depth 2.5" in text
    assert 'stale_bucket{le="1"} 1' in text
    assert 'stale_bucket{le="+Inf"} 2' in text
    assert "stale_sum 5.5" in text and "stale_count 2" in text
    js = reg.to_json()
    assert json.loads(json.dumps(js)) == js
    assert js["up_total"]["samples"][0]["value"] == 3
    with pytest.raises(ValueError):
        reg.gauge("up_total")  # name already a counter
    with pytest.raises(ValueError):
        Counter().inc(-1)


def test_from_engine_snapshot(traced_pair):
    _, eb, _, _ = traced_pair
    reg = from_engine(eb)
    js = reg.to_json()

    def val(name):
        return js[name]["samples"][0]["value"]

    assert val("safl_rounds_total") == eb.t_global == 4
    assert val("safl_tx_bytes_total") == eb.tx_bytes
    assert val("safl_rx_bytes_total") == eb.rx_bytes
    assert val("safl_clients") == len(eb.clients)
    stale = js["safl_staleness"]["samples"][0]
    assert stale["count"] == sum(eb.staleness_hist.values())
    text = reg.to_prometheus()
    assert "# TYPE safl_staleness histogram" in text
    assert f"safl_rounds_total {eb.t_global}" in text


# ------------------------- profiling hooks -------------------------


def test_compile_log_contract():
    class Srv:
        compile_count = 3

    class Attr:
        folds = 2

    log = (CompileLog().track("srv", Srv())
           .track("fold", Attr(), attr="folds"))
    assert log.counts() == {"srv": 3, "fold": 2}
    assert log.assert_exactly("srv", 3) == 3
    assert log.assert_at_most("fold", 2) == 2
    # a target with no count raises: no guard passes vacuously
    log.track("unknown", object())
    with pytest.raises(AttributeError):
        log.assert_exactly("unknown", 99)
    with pytest.raises(AssertionError):
        log.assert_exactly("srv", 2)
    with pytest.raises(AssertionError):
        log.assert_at_most("fold", 1)


def test_cache_size_probe():
    fn = jax.jit(lambda x: x + 1)
    fn(1.0)
    assert cache_size(fn) == 1
    with pytest.raises(AttributeError):
        cache_size(object())


def test_engine_compile_log_targets(traced_pair):
    _, eb, _, _ = traced_pair
    log = engine_compile_log(eb)
    counts = log.counts()
    assert "server_step" in counts and "wave" in counts
    log.assert_exactly("server_step", 1)


def test_run_flushes_ring_exactly_once(setup):
    """The one-host-transfer-per-run invariant, now observable: a full
    traced run crosses the metrics ring to the host exactly once per
    flush channel."""
    with TransferScope() as ts:
        _run(setup, trace_level="upload")
    assert ts.count("metrics_ring.flush") == 1
    assert ts.count("metrics_ring.flush_sched") == 1


# ------------------------- DeviceMetricsRing -------------------------


def test_ring_growth_preserves_rows():
    """Appending past the allocated capacity doubles the buffer; every
    row written before the growth survives it (tracing-era metric rings
    outlive their capacity hint under timeout horizons)."""
    ring = DeviceMetricsRing(capacity=3)  # allocates the 64-row floor
    n = 70  # forces one doubling
    for i in range(n):
        ring.append(float(i), float(i) + 0.5, float(i) * 2.0)
    assert len(ring) == n and ring.capacity == 128
    rows = ring.flush()
    assert rows.shape == (n, 3)
    np.testing.assert_array_equal(rows[:, 0], np.arange(n, dtype=np.float32))
    np.testing.assert_array_equal(
        rows[:, 1], np.arange(n, dtype=np.float32) + 0.5)
    np.testing.assert_array_equal(
        rows[:, 2], np.arange(n, dtype=np.float32) * 2.0)


def test_ring_sched_sentinels_never_leak():
    """append_sched pads odd K to the next power of two with drop-mode
    sentinels; neither histogram nor participation may ever count one,
    and over-range staleness clips into the overflow bin."""
    ring = DeviceMetricsRing(4, stale_bins=4, n_clients=3)
    ring.append_sched([0, 1, 5], [0, 1, 2])  # K=3 -> padded to 4
    ring.append_sched([0, 0, 0], [1, 1, 1])  # padded again
    ring.append_sched([2], [0])  # K already a power of two
    hist, part = ring.flush_sched()
    assert hist.shape == (4,) and part.shape == (3,)
    # 7 real entries in, exactly 7 out — sentinels dropped, 5 clipped
    # into the overflow bin 3
    np.testing.assert_array_equal(hist, [4, 1, 1, 1])
    np.testing.assert_array_equal(part, [2, 4, 1])
    assert int(hist.sum()) == int(part.sum()) == 7


def test_ring_flush_is_one_transfer():
    ring = DeviceMetricsRing(4)
    ring.append(1.0, 2.0, 3.0)
    with TransferScope() as ts:
        ring.flush()
    assert ts.delta() == {"metrics_ring.flush": 1}
