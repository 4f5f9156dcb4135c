"""The comparison that decides ``correct``: the program's first rounds
against the plain reference (``bench/reference.py``) on the same seed.
The checked rounds reach the steady state of the window: in the last of
them most uploads come from clients that uploaded before and trained
from a global model they adopted; a run in which the reference counts
none of these is not correct.

Nine numbers (the last only where the wire keeps residuals), each a
relative gap between two readings of the same quantity (never the norm
of a difference, so client training's sensitivity to rounding does not
count against the program):

* ``loss``: the largest gap, over the checked rounds, between the
  program's evaluation loss and the reference's, over the reference's.
* ``grad``: the first round's server update (global weights before minus
  after round 1, the step the server optimizer applied), by the worst
  leaf: ``|norm(program leaf) - norm(reference leaf)|`` over the larger
  of the reference leaf's norm and the median leaf's.
* ``last``: the same for the last checked round's server update.
* ``change``: the same for the global weights' change over all the
  checked rounds.
* ``grad_med``, ``last_med`` and ``change_med``: the median over the
  leaves of the same per-leaf gaps.  The worst leaf is a small leaf whose
  norm moves by a tenth or more between sound runs, which hides a fault
  such as half of a horizon's uploads left out; the median leaf is
  steady from seed to seed and sees it.
* ``drift_med``: each round's server update, by the median over the
  leaves of the signed gaps (program's norm above the reference's is
  positive), averaged over the checked rounds.  Rounding moves it either
  way and averages out; an update taken over half of a horizon's uploads
  is larger on every round (the mean of fewer uploads keeps more of their
  spread), so this sees that fault where the uploads agree closely.

* ``residual`` (on a wire with error feedback): the error-feedback
  residual each client holds after the checked rounds, by the worst
  client: ``|norm(program's) - norm(reference's)|`` over the larger of
  the two, over every client either side holds one for.  A residual is
  what the wire dropped from the client's last upload; the global
  weights cannot show it, since the quantisation error of an upload moves
  a leaf's norm by far less than the program's rounding does.  A program
  that keeps no residual reads 1 on every client, and so does one that
  zeroes a residual where the reference keeps it.  Absent where neither
  side holds a residual.

Leaves whose first-round update in the reference is under a thousandth
of the median leaf's are left out of both (none is at present; the rule
is there for weights that only round-off moves).
"""
from __future__ import annotations

import numpy as np

#: leaves whose reference update is below this share of the median
#: leaf's are not compared
NEGLIGIBLE = 1e-3
#: the numbers compared, in the order they are printed
NUMBERS = ("loss", "grad", "grad_med", "last", "last_med", "change",
           "change_med", "drift_med", "residual")


def _leaves(tree) -> dict:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): np.asarray(v, np.float64)
            for k, v in flat}


def _diff(a: dict, b: dict) -> dict:
    return {k: a[k] - b[k] for k in a}


def _signed_gaps(prog: dict, ref: dict, keep: list) -> dict:
    """Per leaf: (program's norm - reference's) over the larger of the
    reference leaf's norm and the median leaf's."""
    np_ = {k: float(np.linalg.norm(prog[k])) for k in keep}
    nr = {k: float(np.linalg.norm(ref[k])) for k in keep}
    med = float(np.median(list(nr.values())))
    return {k: (np_[k] - nr[k]) / max(nr[k], med, 1e-30) for k in keep}


def _leaf_gaps(prog: dict, ref: dict, keep: list) -> tuple:
    """(worst gap, its leaf, median gap) of the per-leaf norm gaps."""
    if not keep:  # a reference that is not finite keeps no leaf
        return float("inf"), "", float("inf")
    gaps = {k: abs(v) for k, v in _signed_gaps(prog, ref, keep).items()}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst, float(np.median(list(gaps.values())))


def _drift(pp: list, rp: list, keep: list) -> float:
    """The mean over the rounds of each round's median signed leaf gap of
    the server update, as a magnitude."""
    if not keep:
        return float("inf")
    per_round = [float(np.median(list(_signed_gaps(
        _diff(pp[i - 1], pp[i]), _diff(rp[i - 1], rp[i]), keep).values())))
        for i in range(1, len(rp))]
    return abs(float(np.mean(per_round)))


def _residual_gap(prog: dict, ref: dict) -> float:
    """The worst client's gap between the norms of its residual in the
    program and in the reference (a missing residual reads 0)."""
    gaps = [abs(prog.get(c, 0.0) - ref.get(c, 0.0))
            / max(prog.get(c, 0.0), ref.get(c, 0.0), 1e-30)
            for c in set(prog) | set(ref)]
    return max(gaps)


def readings(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref``: ``params`` (global weights before round 1 and
    after each checked round) and ``losses`` (after each checked round);
    ``ref`` also counts ``reuploads`` and ``adopted`` uploads.  Either may
    hold ``residuals``, the norms of the clients' error-feedback residuals
    by client id.  Returns each compared number with the leaf that set
    it, and those counts."""
    pp = [_leaves(t) for t in prog["params"]]
    rp = [_leaves(t) for t in ref["params"]]
    r = len(rp) - 1
    assert len(pp) == r + 1 and r >= 1, (len(pp), len(rp))
    assert set(pp[0]) == set(rp[0]), "weight layouts differ"
    g_ref, g_prog = _diff(rp[0], rp[1]), _diff(pp[0], pp[1])
    norms = {k: float(np.linalg.norm(v)) for k, v in g_ref.items()}
    floor = NEGLIGIBLE * float(np.median(list(norms.values())))
    keep = sorted(k for k, v in norms.items() if v >= floor)
    out = {}
    lp, lr = np.asarray(prog["losses"][:r]), np.asarray(ref["losses"][:r])
    if np.all(np.isfinite(lp)):
        out["loss"] = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    else:
        out["loss"] = float("inf")
    out["grad"], out["grad_leaf"], out["grad_med"] = _leaf_gaps(
        g_prog, g_ref, keep)
    out["last"], out["last_leaf"], out["last_med"] = _leaf_gaps(
        _diff(pp[r - 1], pp[r]), _diff(rp[r - 1], rp[r]), keep)
    out["change"], out["change_leaf"], out["change_med"] = _leaf_gaps(
        _diff(pp[r], pp[0]), _diff(rp[r], rp[0]), keep)
    out["drift_med"] = _drift(pp, rp, keep)
    res_p, res_r = prog.get("residuals", {}), ref.get("residuals", {})
    if res_p or res_r:
        out["residual"] = _residual_gap(res_p, res_r)
    for key in NUMBERS:
        if key not in out:
            continue
        if not np.isfinite(out[key]) or not np.all(np.isfinite(lr)):
            out[key] = float("inf")
    out["left_out"] = len(norms) - len(keep)
    out["reuploads"], out["adopted"] = ref["reuploads"], ref["adopted"]
    return out


def verdict(read: dict, limits: dict | None) -> tuple:
    """(correct, [(name, value, limit)]).  Without a limits file nothing
    can be judged, and the run is not correct; nor is it where the checked
    rounds hold no upload trained from an adopted global model (the row
    ``adopted``, whose limit is the least count allowed).  A number whose
    limit is null is printed and not compared: neither the control nor a
    fault reads far enough above the program on it to place a limit."""
    rows = [("adopted", read["adopted"], 1)]
    ok = limits is not None and read["adopted"] >= 1
    for name in NUMBERS:
        if name not in read:  # not read in this cell
            continue
        lim = None if limits is None else limits["limits"].get(name)
        val = read[name]
        rows.append((name, val, lim))
        if lim is not None:
            ok = ok and np.isfinite(val) and val <= lim
    return bool(ok), rows
