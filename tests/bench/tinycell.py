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
    cell.update(cfg=cfg, traffic=tr, limits=TINY_LIMITS)
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
