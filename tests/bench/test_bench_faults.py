"""A run of the benchmark with the timed path broken underneath must
come out not correct: one run per fault a one-chip cell can have, next
to a sound run that comes out correct.  The look for a chip is skipped
(``run.measure``), the rest of a run is driven as on the chip, at a tiny
size on the CPU."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tinycell  # noqa: E402
from tinycell import harness  # noqa: E402

import run  # noqa: E402

harness.use_program()


def _unchanged(monkeypatch):
    """Every server round returns the global weights it was given."""
    from repro.core import aggregation

    orig = aggregation.FlatServer.finalize

    def finalize(self, params_flat, bank, wvec, opt, pprod=1.0):
        _new, opt2, m, zeroed = orig(self, params_flat, bank, wvec, opt,
                                     pprod)
        return params_flat, opt2, m, zeroed

    monkeypatch.setattr(aggregation.FlatServer, "finalize", finalize)


def _drop_half(monkeypatch):
    """Every second upload never reaches the accumulator, so the mean is
    taken over the rest."""
    from repro.core import flatbuf

    orig = flatbuf.AccumBuffer.fold
    seen = [0]

    def fold(self, payload, **kw):
        seen[0] += 1
        if seen[0] % 2:
            orig(self, payload, **kw)

    monkeypatch.setattr(flatbuf.AccumBuffer, "fold", fold)


def _alter_one(monkeypatch):
    """The first upload of every client wave is negated where the wave
    program produces it."""
    from repro.core import safl

    orig = safl.make_batched_hetero_train

    def make(*a, **kw):
        fn = orig(*a, **kw)

        def wave(*args):
            vecs, new_flat, states, losses = fn(*args)
            return vecs.at[0].multiply(-1.0), new_flat, states, losses

        return wave

    monkeypatch.setattr(safl, "make_batched_hetero_train", make)


def _no_adopt(monkeypatch):
    """No client ever adopts a published global model: every client reads
    as already holding the newest round, so it keeps training its own
    weights and BatchNorm state."""
    from repro.core import client

    monkeypatch.setattr(client.ClientState, "version",
                        property(lambda self: 1 << 30,
                                 lambda self, value: None), raising=False)


FAULTS = {"none": None, "unchanged": _unchanged, "drop_half": _drop_half,
          "alter_one": _alter_one, "no_adopt": _no_adopt}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_is_caught(fault, monkeypatch, capsys):
    name = "resnet18.as-f32"
    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch)
    rc = run.measure(tinycell.args(name, seconds=0.5),
                     harness.benchmark(), tinycell.tiny_cell(name),
                     tinycell.peak())
    assert rc == 0
    line = tinycell.last_json(capsys.readouterr().out)
    assert line["correct"] is (fault == "none"), line["compared"]
