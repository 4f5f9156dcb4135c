"""What every cell shares: finding a cell's files by name, making its
inputs from the seed, building the engine, and driving it one
aggregation round at a time.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  The configuration is
``bench/configs/<config>.json`` (sizes) with its plain reference
``bench/configs/<config>.py`` beside it; the traffic mix is
``bench/traffic/<traffic>.json`` (engine settings and population);
the limits of the correctness comparison are ``bench/limits/<cell>.json``.
Adding a cell adds files; nothing here names a cell.

A configuration's JSON names its module (``reference``, a path from the
checkout's root), the program's model function (``program_apply``,
``program_kwargs``) and its ``kind``, and holds every size its module
reads.  The module says what the configuration's inputs, targets,
weights and work are; it imports nothing of the program and provides:

* ``make_data(cfg, traffic, key) -> dict``: ``xs`` and ``ys`` as
  (clients, batches, batch, ...) and ``test_x``, ``test_y``, as host
  arrays, from a JAX key.  ``ys`` holds one label per sample or one
  target per position; targets come already shifted (a next-token
  target sits at its input's position), so no shift lives in the
  reference.  Integer inputs are token ids and stay integers.
* ``init(cfg, key)``: ``(params, state)`` or ``(params, state,
  frozen)``, jittable.  ``params`` is the trained part: what a client
  trains and uploads, the server aggregates and ``check.py`` compares;
  its element count is the uploaded row's length (``work.row_length``).
  ``state`` is the non-trained model state (BatchNorm statistics).
  ``frozen`` is shared by every client and never trained, uploaded,
  averaged or copied per client; ``build_engine`` hands it to the
  engine as ``FLEngine(..., frozen=frozen)`` only where it exists.
* ``apply(cfg, params, state, x, train, frozen=None)``: the plain
  forward pass, ``(logits (..., classes), new state)``; the reference
  passes ``frozen`` in training and in evaluation.
* ``flops_forward(cfg)`` and ``flops_train(cfg)``: model FLOPs of one
  sample's forward pass and of its training step, from shapes held in
  the configuration file (``work.round_flops``).

The traffic generator gives every seed the same population: client
speeds, link times and start offsets are fixed quantiles of their laws
(lognormal, lognormal, uniform), in a fixed pairing, and the seed only
permutes which client gets which triple (and draws the data and the
weights).  So every seed has the same arrivals and asks for the same
work; only the clients' labels and data differ.
"""
from __future__ import annotations

import functools
import importlib
import importlib.util
import json
import os
import sys
from statistics import NormalDist

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
#: the persistent compile cache, at a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: environment variables that force a non-default server backend
OVERRIDES = ("REPRO_AGG_BACKEND", "REPRO_PALLAS_INTERPRET")


def use_program() -> None:
    """Make the program under test importable (``src/`` of the checkout)."""
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a Python file of the benchmark by its path."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return read_json(os.path.join(ROOT, "BENCHMARK.json"))


@functools.lru_cache(maxsize=None)
def _config_module(path: str):
    stem = os.path.splitext(os.path.basename(path))[0]
    return load_module(path, "bench_config_" + stem.replace("-", "_"))


def config_module(cfg: dict):
    """The plain reference module a configuration names (``reference``,
    relative to the checkout's root), imported once per process."""
    return _config_module(os.path.join(ROOT, cfg["reference"]))


def find_config(name: str) -> tuple:
    """A configuration's sizes (``bench/configs/<name>.json``) and its
    plain reference module (``bench/configs/<name>.py``), by name."""
    cfg = read_json(os.path.join(BENCH, "configs", name + ".json"))
    return cfg, config_module(cfg)


def find_cell(name: str, bench: dict | None = None) -> dict:
    """Everything one cell needs, found by its name: its BENCHMARK.json
    entry, configuration, reference module, traffic and limits (None
    where no limits file exists yet)."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg, ref = find_config(w["config"])
    traffic = read_json(os.path.join(BENCH, "traffic",
                                     w["traffic"] + ".json"))
    lim_path = os.path.join(BENCH, "limits", name + ".json")
    limits = read_json(lim_path) if os.path.exists(lim_path) else None
    return dict(name=name, entry=w, cfg=cfg, ref=ref, traffic=traffic,
                limits=limits, chips=int(w["chips"]))


def sub_seeds(seed: int, n: int) -> list:
    """``n`` independent 31-bit seeds drawn from ``seed`` (which may be
    any non-negative integer, wider than 32 bits included).  Slots: 0
    data, 1 weights, 2 engine, 3 client permutation."""
    words = np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)
    return [int(w) & 0x7FFFFFFF for w in words]


# ---------------------------------------------------------------------------
# inputs from the seed
# ---------------------------------------------------------------------------


def population(traffic: dict, seed: int) -> dict:
    """Per-client speeds, link times and start offsets.  The multiset of
    (speed, link time, offset) triples is the same for every seed; the
    seed picks which client gets which triple."""
    pop = traffic["population"]
    n = int(pop["n_clients"])
    u = (np.arange(n) + 0.5) / n
    z = np.asarray([NormalDist().inv_cdf(v) for v in u])
    speed = np.exp(pop["speed_sigma"] * z)
    fixed = np.random.default_rng(0)  # the fixed pairing
    comm = pop["comm_mean_s"] * np.exp(pop["comm_sigma"] * z[
        fixed.permutation(n)])
    offset = pop["start_jitter_s"] * u[fixed.permutation(n)]
    perm = np.random.default_rng(sub_seeds(seed, 4)[3]).permutation(n)
    return dict(speed=[float(v) for v in speed[perm]],
                comm=[float(v) for v in comm[perm]],
                offset=[float(v) for v in offset[perm]],
                jitter_s=float(pop["start_jitter_s"]),
                base_rate=float(pop["base_rate"]),
                samples=int(pop["samples_per_client"]), n=n)


class StartOffset:
    """Stands in for a client's generator, whose one draw in the engine is
    its first upload's start offset, ``uniform(0, jitter)``: it gives the
    traffic's fixed offset instead.  Any other draw fails."""

    def __init__(self, offset: float, span: float):
        self.offset, self.span = offset, span

    def uniform(self, lo: float, hi: float) -> float:
        assert (lo, hi) == (0, self.span), (lo, hi, self.span)
        return self.offset


def make_data(cfg: dict, traffic: dict, seed: int) -> dict:
    """The configuration's inputs and targets from the seed's data
    sub-seed (slot 0), made by its module's ``make_data``."""
    import jax

    key = jax.random.PRNGKey(sub_seeds(seed, 3)[0])
    return config_module(cfg).make_data(cfg, traffic, key)


def make_weights(cell: dict, seed: int):
    """``init``'s (params, state) or (params, state, frozen) from the
    seed, made on the device in one call, in the layout the program and
    the reference both read."""
    import jax

    key = jax.random.PRNGKey(sub_seeds(seed, 3)[1])
    init = cell["ref"].init
    cfg = cell["cfg"]
    return jax.jit(lambda k: init(cfg, k))(key)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def program_apply(target: str, kwargs_json: str):
    """The program's model function ``module:attr`` with its keyword
    arguments bound.  Cached, so engines of one process share compiled
    client programs."""
    use_program()
    mod, attr = target.split(":")
    fn = getattr(importlib.import_module(mod), attr)
    kw = json.loads(kwargs_json)
    return functools.partial(fn, **kw) if kw else fn


def build_engine(cell: dict, seed: int, data: dict, weights):
    """An ``FLEngine`` for the cell on the seed's inputs.  The engine's
    own speed and link-time draws are replaced by the traffic's
    population before the first round builds its event heap."""
    use_program()
    from repro.configs.base import FLConfig
    from repro.core import FLEngine

    cfg, traffic = cell["cfg"], cell["traffic"]
    pop = population(traffic, seed)
    fl = FLConfig(**traffic["engine"], n_clients=pop["n"],
                  devices=cell["chips"], seed=sub_seeds(seed, 3)[2],
                  speed_sigma=traffic["population"]["speed_sigma"],
                  comm_mean_s=traffic["population"]["comm_mean_s"])
    per = pop["samples"]
    nb, b = data["xs"].shape[1:3]
    shards = [dict(xs=data["xs"][i], ys=data["ys"][i],
                   mask=np.ones((nb, b), np.float32), n=per)
              for i in range(pop["n"])]
    params, state, *frozen = weights
    apply_fn = program_apply(cfg["program_apply"],
                             json.dumps(cfg["program_kwargs"],
                                        sort_keys=True))
    extra = dict(frozen=frozen[0]) if frozen else {}
    eng = FLEngine(fl, apply_fn, cfg["kind"], params, state, shards,
                   data["test_x"], data["test_y"], **extra)
    assert abs(pop["base_rate"] * eng.clients[0].speed
               * eng._base_compute(eng.clients[0])
               - per * fl.local_epochs) < 1e-6 * per, \
        "the engine's simulated compute rate differs from the traffic's"
    for c in eng.clients:
        c.speed = pop["speed"][c.cid]
        c.comm_time = pop["comm"][c.cid]
        c.rng = StartOffset(pop["offset"][c.cid], pop["jitter_s"])
    return eng


def run_round(eng):
    """One aggregation round through the engine's own entry; returns its
    ``FLResult`` (the round's metrics are on the host when it returns)."""
    return eng.run(eng.t_global + 1)


@functools.lru_cache(maxsize=None)
def _norm():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda r: jnp.linalg.norm(r.astype(jnp.float32)))


def residual_norms(eng) -> dict:
    """The norm of each client's error-feedback residual the engine holds
    (``FLEngine._residuals``), by client id: none off a lossy wire."""
    import jax

    held = getattr(eng, "_residuals", {})
    norms = jax.device_get({int(c): _norm()(r) for c, r in held.items()})
    return {c: float(v) for c, v in norms.items()}


def checked_rounds(eng, n: int, p0) -> dict:
    """Drive the engine through its first ``n`` rounds (the rounds the
    reference replays) and record the global weights before and after
    each (host pytrees), each round's evaluation loss, and the norms of
    the error-feedback residuals the clients hold after the last."""
    import jax

    out = dict(params=[p0], losses=[])
    for _ in range(n):
        res = run_round(eng)
        out["params"].append(jax.device_get(res.final_params))
        out["losses"].append(float(res.metrics.records[-1].loss))
    out["residuals"] = residual_norms(eng)
    return out


def prewarm_ring(max_rounds: int) -> None:
    """Compile the metrics-ring programs for every capacity a run of up
    to ``max_rounds`` rounds reaches: the engine sizes its per-call ring
    by the total round count, so each power of two past 64 is a new
    shape."""
    use_program()
    from repro.core.metrics import DeviceMetricsRing

    cap = 64
    while cap <= max_rounds * 2:
        ring = DeviceMetricsRing(cap, channels=5, stale_bins=32,
                                 n_clients=1)
        ring.append(*([np.float32(0.0)] * 5))
        ring.flush()
        cap *= 2


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache
    (JAX's backend-compile events), by function name."""

    def __init__(self):
        import jax

        self.names: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.names.append(kw.get("fun_name", "?"))

    def __len__(self) -> int:
        return len(self.names)


_COUNTER: list = []


def compile_counter() -> CompileCounter:
    """The process's one compile counter (JAX keeps every listener it is
    given, so a second would count twice as long as the process lives)."""
    if not _COUNTER:
        _COUNTER.append(CompileCounter())
    return _COUNTER[0]
