"""Wall-clock stage spans of the batched semi-async engine: the
``safl.*`` ``jax.profiler.TraceAnnotation`` spans that ``FLEngine.run``
writes into the profiler's host plane (table in ``repro/obs/README.md``).

A tiny engine (small CNN, f32 wire, streaming channel) runs under the
profiler on the CPU; the ``.xplane.pb`` is read back and checked for:

  * one ``safl.round`` per round, carrying ``uploads == K``, whose waves'
    ``lanes`` sum to K;
  * the span tree: every stage lies inside its parent;
  * the ``round`` stat joins the SpanTracer's round records;
  * the profiler changes nothing the engine computes;
  * ``safl.gather`` and ``safl.refresh`` move a model's whole state tree
    in one program: on ResNet-18 (40 BatchNorm leaves) at most two
    programs run inside either span, and ``leaves`` reads the tree's
    leaf count (0 on the CNN).
"""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import FLConfig
from repro.core import FLEngine
from repro.data import build_client_shards, make_dataset, train_test_split
from repro.models.vision_cnn import build_paper_model
from repro.obs.profile import jax_profile

K, ROUNDS = 3, 4

#: the host event of one program execution on jaxlib's CPU client
EXECUTE = "PjRtCpuExecutable::Execute"

#: each stage span and the span it lies inside
PARENT = {"safl.ring": "safl.run", "safl.round": "safl.run",
          "safl.flush": "safl.run", "safl.unravel": "safl.run",
          "safl.pop": "safl.round", "safl.wave": "safl.round",
          "safl.finalize": "safl.round", "safl.state": "safl.round",
          "safl.eval": "safl.round",
          "safl.gather": "safl.wave", "safl.train": "safl.wave",
          "safl.encode": "safl.wave", "safl.fold": "safl.wave",
          "safl.refresh": "safl.wave"}


def _engine(setup):
    shards, te, p0, s0, apply_fn = setup
    cfg = FLConfig(n_clients=6, k=K, mode="semi_async",
                   aggregation="fedbuff", client_lr=0.05, server_lr=0.05,
                   target_accuracy=0.3, trace_level="upload")
    return FLEngine(cfg, apply_fn, "image", p0, s0, shards, te.x[:100],
                    te.y[:100])


def _drive(eng):
    """Two run() calls: up to round 2, then up to ROUNDS."""
    eng.run(2)
    return eng.run(ROUNDS)


def _stage_spans(path):
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("safl."):
                    out.append((e.name, int(e.start_ns), int(e.end_ns),
                                {k: int(v) for k, v in e.stats}))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _executions(path):
    """Start times of the programs executed on the host's CPU client."""
    return sorted(int(e.start_ns)
                  for plane in ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name == EXECUTE)


def _trace(model, tmp_path_factory, **kw):
    """``(engine, result, untraced result, spans, executions)`` of the
    tiny engine on ``model``, traced after an untraced run compiled its
    programs."""
    ds = make_dataset("cifar10", n=240, seed=0, hw=16)
    tr, te = train_test_split(ds)
    shards = build_client_shards(tr, "iid", n_clients=6, batch_size=16)
    p0, s0, apply_fn = build_paper_model(model, jax.random.PRNGKey(0), **kw)
    setup = (shards, te, p0, s0, apply_fn)
    plain = _engine(setup)
    res_plain = _drive(plain)
    eng = _engine(setup)
    trace_dir = str(tmp_path_factory.mktemp("stages"))
    with jax_profile(trace_dir):
        res = _drive(eng)
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return eng, res, res_plain, _stage_spans(path), _executions(path)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return _trace("cnn", tmp_path_factory, width=4, image_size=16)


@pytest.fixture(scope="module", params=[2, 4], ids=["w2", "w4"])
def traced_resnet(request, tmp_path_factory):
    return _trace("resnet18", tmp_path_factory, width=request.param)


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_one_round_span_per_round_with_k_uploads(traced):
    _eng, _res, _plain, spans, _execs = traced
    rounds = _named(spans, "safl.round")
    assert [s[3]["round"] for s in rounds] == list(range(1, ROUNDS + 1))
    assert all(s[3]["uploads"] == K for s in rounds)
    for rnd in rounds:
        waves = [s for s in _named(spans, "safl.wave") if _inside(s, rnd)]
        assert len(waves) == rnd[3]["waves"] >= 1
        assert sum(s[3]["lanes"] for s in waves) == K
        assert [s[3]["wave"] for s in waves] == list(range(len(waves)))
        assert all(s[3]["bucket"] >= s[3]["lanes"] for s in waves)
        pops = [s for s in _named(spans, "safl.pop") if _inside(s, rnd)]
        assert len(pops) == 1
        assert pops[0][3]["admitted"] == K <= pops[0][3]["popped"]
    # the streaming channel folds every upload; no defense skips any
    folds = _named(spans, "safl.fold")
    assert sum(s[3]["folds"] for s in folds) == K * ROUNDS
    assert all(s[3]["skipped"] == 0 for s in folds)


def test_stage_spans_nest(traced):
    _eng, _res, _plain, spans, _execs = traced
    runs = _named(spans, "safl.run")
    assert [s[3]["rounds"] for s in runs] == [2, ROUNDS - 2]
    assert set(PARENT) <= {s[0] for s in spans}
    for child in spans:
        if child[0] == "safl.run":
            continue
        parents = _named(spans, PARENT[child[0]])
        assert sum(_inside(child, p) for p in parents) == 1, child
    for run in runs:  # one ring, flush and unravel per run() call
        for name in ("safl.ring", "safl.flush", "safl.unravel"):
            assert sum(_inside(s, run) for s in _named(spans, name)) == 1


def test_round_stat_joins_the_span_tracer(traced):
    eng, _res, _plain, spans, _execs = traced
    sim = [r["round"] for r in eng.tracer.records if r.get("name") == "round"]
    assert sim == [s[3]["round"] for s in _named(spans, "safl.round")]


def test_profiler_changes_nothing_the_engine_computes(traced):
    _eng, res, plain, _spans, _execs = traced
    for a, b in zip(jax.tree_util.tree_leaves(res.final_params),
                    jax.tree_util.tree_leaves(plain.final_params),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert res.metrics.records == plain.metrics.records
    assert len(res.metrics.records) == ROUNDS



def _programs_in(span, execs):
    return sum(span[1] <= t <= span[2] for t in execs)


def _state_stage_programs(spans, execs):
    """Programs run inside each ``safl.gather`` and ``safl.refresh`` span,
    beside the span's stats; fails loudly when the trace holds no
    execution event to count."""
    assert execs, f"no {EXECUTE} event in the trace: nothing to count"
    return [(s[0], _programs_in(s, execs), s[3]) for s in spans
            if s[0] in ("safl.gather", "safl.refresh")]


def test_state_stages_run_one_program_per_move_on_resnet(traced_resnet):
    """A gather stacks the lanes' rows and states in one program; a
    refresh is the last slot's state slice and one program for every
    continuing client's row and state.  Neither grows with the tree's 40
    leaves nor with the lanes sliced (the schedule slices up to four in
    a wave)."""
    _eng, _res, _plain, spans, execs = traced_resnet
    stages = _state_stage_programs(spans, execs)
    assert {name for name, _, _ in stages} == {"safl.gather",
                                                "safl.refresh"}
    for name, programs, stats in stages:
        moved = name == "safl.gather" or stats["sliced"] > 0
        assert stats["leaves"] == (40 if moved else 0), (name, stats)
        if name == "safl.gather":
            assert programs == 1, (programs, stats)
        else:
            assert programs <= min(2, stats["sliced"]), (programs, stats)
    assert max(stats.get("sliced", 0) for _, _, stats in stages) > 2


def test_state_stages_of_a_stateless_model_move_no_leaves(traced):
    """The CNN has no BatchNorm: ``leaves`` reads 0, and each gather and
    refresh runs one program at most, the one that stacks or slices the
    rows (its empty state trees ride along in the same program)."""
    _eng, _res, _plain, spans, execs = traced
    stages = _state_stage_programs(spans, execs)
    assert {name for name, _, _ in stages} == {"safl.gather",
                                                "safl.refresh"}
    for name, programs, stats in stages:
        assert stats["leaves"] == 0, (name, stats)
        assert programs <= 1, (name, programs, stats)
    assert all(programs == 1 for name, programs, _ in stages
               if name == "safl.gather")
