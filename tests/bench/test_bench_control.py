"""The control of the correctness comparison at a size a test run holds:
the reference computed in bfloat16 in the program's place, and the
reference with a fault planted, each fail at least one compared number;
the program itself passes them all."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tinycell  # noqa: E402

import check  # noqa: E402
import control  # noqa: E402


def test_control_and_planted_faults_fail_the_program_passes():
    cell = tinycell.tiny_cell("resnet18.as-f32")
    out = control.seed_readings(cell, 2**33 + 5, program=True, control=True,
                                faults=True)
    limits = tinycell.TINY_LIMITS
    assert check.verdict(out["program"], limits)[0], out["program"]
    assert out["program"]["adopted"] >= 1, out["program"]
    for variant in ("control", "drop_half", "alter_one", "no_adopt"):
        assert not check.verdict(out[variant], limits)[0], (variant,
                                                            out[variant])
        # and by a wide margin on the number that catches it
        worst = max(out[variant][n] / lim
                    for n, lim in limits["limits"].items())
        assert worst > 10, (variant, out[variant])


def _line(seed, program, **variants):
    nums = lambda v: {n: v for n in check.NUMBERS} | {"adopted": 15}
    return {"seed": seed, "program": nums(program),
            **{k: nums(v) for k, v in variants.items()}}


def test_limits_follow_the_rule():
    import setlimits

    lines = [_line(1, 0.01, control=0.5, drop_half=0.05, no_adopt=0.2),
             _line(2, 0.02, control=0.4, drop_half=0.06, no_adopt=0.3)]
    d = setlimits.derive(lines)
    for n in check.NUMBERS:
        assert d["lower"][n] == 0.02
        # drop_half reads under 10x the lower reading and sets nothing;
        # no_adopt's 0.2 is the least that counts
        assert d["upper"][n] == {"value": 0.2, "from": "no_adopt"}
        assert d["limits"][n] == setlimits.round_down(
            0.02 ** 0.4 * 0.2 ** 0.6) == 0.079
    lines = [_line(1, 0.1, control=0.2)]
    assert setlimits.derive(lines)["limits"]["loss"] is None


def test_limits_skip_lines_without_a_number():
    import setlimits

    old = _line(1, 0.5, control=5.0)
    for v in ("program", "control"):
        del old[v]["drift_med"]
    new = _line(2, 0.01, control=0.5)
    d = setlimits.derive([old, new])
    assert d["lower"]["drift_med"] == 0.01
    assert d["lower"]["loss"] == 0.5
    d = setlimits.derive([old])
    assert d["limits"]["drift_med"] is None
    assert d["limits"]["loss"] is not None


def test_wire_faults_count_at_three_times():
    """A wire fault that keeps no residual reads 1 by construction: it
    sets the upper reading from three times the lower one, as a state
    left unchanged does, while a generic fault needs ten."""
    import setlimits

    lines = [_line(1, 0.1, drop_half=0.9, no_ef=1.0)]
    d = setlimits.derive(lines)
    assert d["upper"]["residual"] == {"value": 1.0, "from": "no_ef"}
    assert d["limits"]["residual"] == setlimits.round_down(
        0.1 ** 0.4) == 0.39
    assert setlimits.derive([_line(1, 0.4, no_ef=1.0)])["limits"][
        "residual"] is None
