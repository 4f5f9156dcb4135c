"""A cell of the benchmark cut to a size the CPU runs in seconds: the
real configuration and traffic files, found by name, with the width,
image size and population shrunk.  Shared by the tests in this folder."""
from __future__ import annotations

import copy
import json
import os
import sys
import types

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

#: limits for the tiny cell on the CPU, where the program and the
#: reference both compute in float32 and agree to about 1e-5
TINY_LIMITS = {"limits": {"loss": 1e-3, "grad": 1e-3, "grad_med": 1e-3,
                          "last": 1e-3, "last_med": 1e-3,
                          "change": 1e-3, "change_med": 1e-3,
                          "drift_med": 1e-3}}

#: limits for the tiny cell on the q8 wire.  Codes whose value lies
#: within rounding of a half step flip between the program and the
#: reference, each flip moves a weight by a whole step, and the rounds
#: after it spread the gap: the reference's own uploads perturbed by
#: 1e-5 of each leaf's RMS read ``last`` 0.035 and ``change`` 0.029
#: against it unperturbed.  Sound runs read at most 2e-4 (``loss``),
#: 3.5e-4 (``grad``), 0.039 (``last``), 0.016 (``change``) and 3.1e-4
#: (``last_med``) over four seeds; the bfloat16 control and every
#: planted fault read 0.02 or more on a median number (``grad_med``,
#: ``last_med`` or ``change_med``), so the medians hold the comparison
#: tight and the worst leaves get room.  The clients' residuals: sound
#: runs read at most 0.013 over twelve seeds, a program that keeps none
#: or zeroes an adopting client's reads 1, the control 0.19 or more.
TINY_Q8_LIMITS = {"limits": {"loss": 2e-3, "grad": 5e-3, "grad_med": 1e-3,
                             "last": 0.2, "last_med": 5e-3,
                             "change": 0.1, "change_med": 2e-3,
                             "drift_med": 1e-3, "residual": 0.05}}


def tiny_cell(name: str = "resnet18.as-f32", **traffic_kw) -> dict:
    cell = harness.find_cell(name)
    cfg, tr = dict(cell["cfg"]), copy.deepcopy(cell["traffic"])
    if "width" in cfg:
        cfg.update(width=4, program_kwargs={"width": 4}, image_size=8)
    tr["population"].update(n_clients=8, samples_per_client=32)
    tr["engine"]["k"] = 4
    tr["eval_samples"] = 64
    tr["max_rounds"] = 64
    # enough rounds that 4 uploads train from an adopted global model
    tr["checked_rounds"] = 4
    tr.update(traffic_kw)
    q8 = tr["engine"].get("wire") == "q8"
    cell.update(cfg=cfg, traffic=tr,
                limits=TINY_Q8_LIMITS if q8 else TINY_LIMITS)
    return cell


def peak() -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        return json.load(f)["devices"]["TPU v5 lite"]


def args(name: str, seed: int = 2**31 + 99, seconds: float = 1.0,
         trace: int = 0):
    return types.SimpleNamespace(workload=name, seed=seed, seconds=seconds,
                                 trace=trace)


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])
