"""``bench/roundlog.py``'s summary of a round log, on a hand-built log
whose answers are worked out here."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tinycell import harness  # noqa: E402,F401

import roundlog  # noqa: E402


def built_log():
    """Two set-up rounds, then a window of five 0.1 s rounds from t = 10 s
    of which the third takes 1 s; full collections at 5 s (set-up), 10.25
    s (inside the window) and 20 s (after it)."""
    window = [[10.0, 0.1], [10.1, 0.1], [10.2, 1.0], [11.2, 0.1],
              [11.3, 0.1]]
    return dict(rounds=[[1.0, 3.0], [4.0, 0.5]] + window,
                gc2=[[5.0, 0.05], [10.25, 0.02], [20.0, 0.07]],
                window_rounds=len(window))


def test_summary_of_a_built_log():
    got = roundlog.summary(built_log())
    assert got["rounds"] == 5
    assert got["median_ms"] == pytest.approx(100)
    assert got["stalls_s"] == [1.0]
    assert got["gc2_in_window"] == (1, pytest.approx(0.02))
    assert got["gc2_in_run"] == (3, pytest.approx(0.14))
    assert roundlog.summary(dict(built_log(), window_rounds=0)) == {}


def test_summary_command(tmp_path, capsys):
    path = tmp_path / "a.rounds.json"
    path.write_text(json.dumps(built_log()))
    assert roundlog.main(["--summary", str(path)]) == 0
    name, text = capsys.readouterr().out.split(" ", 1)
    assert name == str(path) and json.loads(text)["stalls_s"] == [1.0]


def test_record_leaves_run_as_it_was(tmp_path):
    """Off the chip ``run.py`` refuses to run: the log is written empty,
    the exit code is ``run.py``'s, and nothing stays patched."""
    import run

    before = (harness.run_round, run.read_layers)
    log = tmp_path / "x.rounds.json"
    rc = roundlog.main(["--log", str(log), "--spans", str(tmp_path / "s"),
                        "--", "--workload", "resnet18.as-f32", "--seed",
                        "3000000019", "--seconds", "1", "--trace", "1"])
    assert rc == 2
    assert json.loads(log.read_text()) == dict(rounds=[], gc2=[],
                                               window_rounds=0)
    assert (harness.run_round, run.read_layers) == before
