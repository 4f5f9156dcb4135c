"""Readings that set the limits of the correctness comparison, several
seeds in one process (one compile), at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 \\
        --program --control --faults --witness

For each seed it prints one JSON line of readings (the numbers of
``bench/check.py``) of:

* ``program``: the engine's checked rounds against the reference, as a
  benchmark run compares them (the lower readings);
* ``control``: the reference computed in bfloat16 (the nearest precision
  below the configuration's float32) in the program's place;
* ``drop_half`` / ``alter_one`` / ``unchanged`` / ``no_adopt``: the
  reference with half of each horizon's uploads left out, with one upload
  negated where the client produces it, with every server step returning
  the global weights unchanged, or with no client ever adopting a
  published global model, in the program's place;
* and, on a cell whose wire keeps error-feedback residuals, the faults
  of that wire: ``no_ef`` and ``f32_wire``, the program with
  ``error_feedback`` off and on the f32 wire; ``no_feedback`` and
  ``reset_residual``, the reference keeping each residual but never
  adding it to the next upload, and zeroing it when the client adopts a
  global model, in the program's place;
* ``witness``: two readings of where the program's gap to the reference
  comes from: ``program_highest``, the program with every matmul and
  convolution at ``highest`` precision, and ``ref_default``, the
  reference in float32 at the TPU's default precision, each against the
  reference.

The benchmark's own runs never run this.  ``tests/bench`` runs it at a
small size.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402


#: the program's settings that plant each fault of the wire in it
PROGRAM_WIRE_FAULTS = {"no_ef": {"error_feedback": False},
                       "f32_wire": {"wire": "f32"}}
#: the faults of the wire planted in the reference
REFERENCE_WIRE_FAULTS = ("no_feedback", "reset_residual")


def with_engine(cell: dict, **change) -> dict:
    """The cell with its engine settings changed."""
    traffic = dict(cell["traffic"])
    traffic["engine"] = {**traffic["engine"], **change}
    return {**cell, "traffic": traffic}


def _program_rounds(cell: dict, seed: int, data: dict, n: int) -> dict:
    import jax

    weights = harness.make_weights(cell, seed)
    eng = harness.build_engine(cell, seed, data, weights)
    prog = harness.checked_rounds(eng, n, jax.device_get(weights[0]))
    del eng, weights
    gc.collect()
    return prog


def seed_readings(cell: dict, seed: int, *, program: bool, control: bool,
                  faults: bool, witness: bool = False) -> dict:
    import jax

    cfg, traffic = cell["cfg"], cell["traffic"]
    n = int(traffic["checked_rounds"])
    data = harness.make_data(cfg, traffic, seed)
    pop = harness.population(traffic, seed)
    out = {"seed": seed}
    if program:
        t0 = time.perf_counter()
        prog = _program_rounds(cell, seed, data, n)
        out["program_s"] = time.perf_counter() - t0
    if witness:
        with jax.default_matmul_precision("highest"):
            prog_hi = _program_rounds(cell, seed, data, n)
    wire = faults and traffic["engine"].get("error_feedback", False) \
        and traffic["engine"].get("wire", "f32") != "f32"
    planted = {}
    if wire:
        for name, change in PROGRAM_WIRE_FAULTS.items():
            planted[name] = _program_rounds(with_engine(cell, **change),
                                            seed, data, n)
    t0 = time.perf_counter()
    ref = reference.run(cfg, cell["ref"], traffic, pop, data,
                        harness.make_weights(cell, seed), n)
    out["reference_s"] = time.perf_counter() - t0
    out["losses"] = ref["losses"]
    if program:
        out["program"] = check.readings(prog, ref)
    if witness:
        out["program_highest"] = check.readings(prog_hi, ref)
    for name, other in planted.items():
        out[name] = check.readings(other, ref)
    variants = []
    if control:
        variants.append(("control", dict(dtype="bfloat16",
                                         precision="default")))
    if faults:
        variants += [(f, dict(fault=f))
                     for f in ("drop_half", "alter_one", "unchanged",
                               "no_adopt")]
    if wire:
        variants += [(f, dict(fault=f)) for f in REFERENCE_WIRE_FAULTS]
    if witness:
        variants.append(("ref_default", dict(precision="default")))
    for name, kw in variants:
        other = reference.run(cfg, cell["ref"], traffic, pop, data,
                              harness.make_weights(cell, seed), n, **kw)
        out[name] = check.readings(other, ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--out", default="", help="also append the lines here")
    args = ap.parse_args(argv)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", harness.CACHE_DIR)
    cell = harness.find_cell(args.workload)
    for seed in args.seeds:
        line = json.dumps(seed_readings(cell, seed, program=args.program,
                                        control=args.control,
                                        faults=args.faults,
                                        witness=args.witness))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
