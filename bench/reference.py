"""Plain reference of semi-asynchronous federated learning, for the
correctness comparison.  It imports nothing of the program.

Semantics (the SAFL paper, arXiv:2405.16086, section 2.2, as the
engine's configuration states them):

* Schedule: client ``c`` finishes a local period every
  ``samples / (base_rate * speed_c) * local_epochs + comm_c`` simulated
  seconds; its first upload lands at that period plus its start offset.
  Uploads are served in order of (time, client id).
* A client trains ``local_epochs`` epochs of plain SGD (learning rate
  ``client_lr``) over its batches in order, from the weights it holds,
  with BatchNorm in training mode.  It uploads its cumulative gradient
  ``(w_start - w_end) / client_lr`` (fedsgd) or its weights (fedavg),
  on the f32 wire.
* After an upload the client adopts the newest global model if one was
  published since the model it trained from, and otherwise keeps its
  own weights.
* Every ``k`` uploads the server aggregates: fedsgd steps the global
  model by ``-server_lr`` times the mean upload and takes the running
  BatchNorm statistics of the horizon's last upload; fedavg takes the
  sample-weighted mean of the uploaded weights and statistics.  Then it
  evaluates the new model on the evaluation set (BatchNorm in evaluation
  mode).

``run`` follows the first rounds of one seed, and counts the uploads of
a client that had uploaded before (``reuploads``) and those trained from
a global model the client adopted (``adopted``): the steady state of a
long run, where every upload is one of these.  ``dtype`` is the type
every weight, activation and update is held and computed in: float32
(with matmuls at ``highest``) is the reference; bfloat16 is the control.
``fault`` plants one of the faults a benchmark run must catch.
"""
from __future__ import annotations

import heapq
import json
from typing import Optional

import numpy as np


def schedule(pop: dict, local_epochs: int):
    """Yields client ids in upload order, forever."""
    heap = []
    for c in range(pop["n"]):
        comp = pop["samples"] / (pop["base_rate"] * pop["speed"][c]) \
            * local_epochs
        t = comp + pop["comm"][c] + pop["offset"][c]
        heapq.heappush(heap, (t, c))
    while True:
        t, c = heapq.heappop(heap)
        comp = pop["samples"] / (pop["base_rate"] * pop["speed"][c]) \
            * local_epochs
        heapq.heappush(heap, (t + comp + pop["comm"][c], c))
        yield c


_PROGRAMS: dict = {}


def _programs(cfg, model, traffic, dtype, precision):
    """Jitted (upload, server, evaluate) of one cell, type and precision;
    built once per process, so seeds after the first compile nothing."""
    key = (json.dumps(cfg, sort_keys=True), model.__name__,
           json.dumps(traffic["engine"], sort_keys=True), str(dtype),
           precision)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = _build(cfg, model, traffic["engine"], dtype,
                                precision)
    return _PROGRAMS[key]


def _build(cfg, model, eng, dtype, precision):
    import jax
    import jax.numpy as jnp

    lr, slr = eng["client_lr"], eng["server_lr"]
    epochs, agg = int(eng["local_epochs"]), eng["aggregation"]
    tree = jax.tree_util.tree_map

    def ce(logits, y):
        logits = logits.astype(jnp.float32) if dtype == jnp.float32 \
            else logits
        logz = jax.nn.logsumexp(logits, axis=-1)
        return jnp.mean(logz - jnp.take_along_axis(
            logits, y[:, None], axis=-1)[:, 0])

    def loss(p, s, x, y):
        logits, news = model.apply(cfg, p, s, x, True)
        return ce(logits, y), news

    grad = jax.grad(loss, has_aux=True)

    def step(carry, batch):
        p, s = carry
        g, s = grad(p, s, *batch)
        p = tree(lambda a, b: (a - lr * b).astype(dtype), p, g)
        s = tree(lambda a: a.astype(dtype), s)
        return (p, s), None

    @jax.jit
    def upload(p, s, xs, ys):
        """One client's local epochs and its upload."""
        p_end, s_end = p, s
        for _ in range(epochs):
            (p_end, s_end), _ = jax.lax.scan(step, (p_end, s_end), (xs, ys))
        up = (tree(lambda a, b: ((a - b) / lr).astype(dtype), p, p_end)
              if agg == "fedsgd" else p_end)
        return p_end, s_end, up

    @jax.jit
    def server(g, ups, states):
        """The server step over one horizon's (kept) uploads."""
        mean = tree(lambda *a: (sum(a[1:], a[0]) / len(a)).astype(dtype),
                    *ups)
        if agg == "fedsgd":
            return (tree(lambda a, b: (a - slr * b).astype(dtype), g, mean),
                    states[-1])
        # fedavg: equal sample counts, so the weighted mean is the mean
        return mean, tree(
            lambda *a: (sum(a[1:], a[0]) / len(a)).astype(dtype), *states)

    @jax.jit
    def evaluate(p, s, x, y):
        logits, _ = model.apply(cfg, p, s, x, False)
        return ce(logits, y).astype(jnp.float32)

    def with_precision(fn):
        def run(*a):
            with jax.default_matmul_precision(precision):
                return fn(*a)
        return run

    return tuple(with_precision(f) for f in (upload, server, evaluate))


def run(cfg, model, traffic: dict, pop: dict, data: dict, weights,
        rounds: int, *, dtype: str = "float32", precision: str = "highest",
        fault: Optional[str] = None) -> dict:
    """The first ``rounds`` aggregation rounds of one seed.

    Returns ``params`` (the global weights before round 1 and after each
    round, as host pytrees in float32), ``losses`` (the evaluation loss
    after each round), and the counts ``uploads``, ``reuploads`` and
    ``adopted``.

    ``fault``: ``"drop_half"`` leaves every second upload of a horizon
    out of the aggregation (the mean is taken over the rest);
    ``"alter_one"`` negates the first upload of every horizon where the
    client produces it; ``"unchanged"`` makes every server step return
    the global weights and statistics it was given; ``"no_adopt"`` has
    every client keep its own weights after an upload, never adopting a
    published global model.
    """
    import jax
    import jax.numpy as jnp

    eng = traffic["engine"]
    dt = jnp.dtype(dtype)
    k = int(eng["k"])
    assert eng["aggregation"] in ("fedsgd", "fedavg"), eng["aggregation"]
    assert eng.get("wire", "f32") == "f32", eng.get("wire")
    upload, server, evaluate = _programs(cfg, model, traffic, dt, precision)
    tree = jax.tree_util.tree_map
    cast = lambda t: tree(lambda a: jnp.asarray(a).astype(dt), t)
    p0, s0 = cast(weights[0]), cast(weights[1])
    xs = jnp.asarray(data["xs"]).astype(dt)
    ys = jnp.asarray(data["ys"])
    tx = jnp.asarray(data["test_x"]).astype(dt)
    ty = jnp.asarray(data["test_y"])
    n = pop["n"]
    cp, cs = [p0] * n, [s0] * n
    version, uploaded, from_global = [0] * n, [False] * n, [False] * n
    g, gs, t = p0, s0, 0
    out = dict(params=[tree(lambda a: np.asarray(a, np.float32), p0)],
               losses=[], uploads=0, reuploads=0, adopted=0)
    buf = []
    sched = schedule(pop, int(eng["local_epochs"]))
    while t < rounds:
        c = next(sched)
        p_end, s_end, up = upload(cp[c], cs[c], xs[c], ys[c])
        out["uploads"] += 1
        out["reuploads"] += uploaded[c]
        out["adopted"] += from_global[c]
        uploaded[c] = True
        if fault == "alter_one" and not buf:
            up = tree(lambda a: -a, up)
        buf.append((up, s_end))
        if version[c] < t and fault != "no_adopt":
            cp[c], cs[c], version[c] = g, gs, t
            from_global[c] = True
        else:
            cp[c], cs[c] = p_end, s_end
            from_global[c] = False
        if len(buf) < k:
            continue
        kept = buf[::2] if fault == "drop_half" else buf
        if fault != "unchanged":
            g, gs = server(g, [u for u, _ in kept], [s for _, s in kept])
        t += 1
        buf = []
        out["params"].append(tree(lambda a: np.asarray(a, np.float32), g))
        out["losses"].append(float(evaluate(g, gs, tx, ty)))
    return out
