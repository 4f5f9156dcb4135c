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
  with BatchNorm in training mode.  The loss is the cross-entropy
  averaged over every target (one per sample, or one per position).  A
  frozen part of the weights, where the configuration has one, is read
  by every client and never trained.  It uploads its cumulative gradient
  ``(w_start - w_end) / client_lr`` (fedsgd) or its weights (fedavg).
* The wire: ``f32`` carries the upload as it is.  ``q8`` (fedsgd with
  ``error_feedback``) ravels it in leaf order, pads it with zeros to a
  multiple of ``quant_block`` and adds the client's residual (zero
  before its first upload), then quantises each block of
  ``quant_block`` values ``x`` to int8 codes ``q = clip(round(x /
  scale), -127, 127)`` with ``scale = max(|x|) / 127`` (at least
  1e-12); the server sees ``q * scale`` and the residual becomes ``x -
  q * scale``.  Other wires, and q8 without error feedback or on model
  uploads, are refused.
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
long run, where every upload is one of these.  On the q8 wire it also
returns the norm of every residual the clients hold after those rounds.
``dtype`` is the type every weight, activation and update is held and
computed in: float32 (with matmuls at ``highest``) is the reference;
bfloat16 is the control.
Integer inputs (token ids) stay integers in either.
``fault`` plants one of the faults a benchmark run must catch.
"""
from __future__ import annotations

import functools
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
        """Mean over every target: ``logits (..., C)``, ``y (...)``."""
        logits = logits.reshape(-1, logits.shape[-1])
        y = y.reshape(-1)
        logits = logits.astype(jnp.float32) if dtype == jnp.float32 \
            else logits
        logz = jax.nn.logsumexp(logits, axis=-1)
        return jnp.mean(logz - jnp.take_along_axis(
            logits, y[:, None], axis=-1)[:, 0])

    def loss(p, s, x, y, frozen):
        logits, news = model.apply(cfg, p, s, x, True, frozen=frozen)
        return ce(logits, y), news

    grad = jax.grad(loss, has_aux=True)

    @jax.jit
    def upload(p, s, xs, ys, frozen):
        """One client's local epochs and its upload."""
        def step(carry, batch):
            p, s = carry
            g, s = grad(p, s, *batch, frozen)
            p = tree(lambda a, b: (a - lr * b).astype(dtype), p, g)
            s = tree(lambda a: a.astype(dtype), s)
            return (p, s), None

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
    def evaluate(p, s, x, y, frozen):
        logits, _ = model.apply(cfg, p, s, x, False, frozen=frozen)
        return ce(logits, y).astype(jnp.float32)

    def with_precision(fn):
        def run(*a):
            with jax.default_matmul_precision(precision):
                return fn(*a)
        return run

    return tuple(with_precision(f) for f in (upload, server, evaluate))


def quantize_q8(x, qblock: int):
    """Blockwise int8 codes of a padded flat ``x`` (its length a multiple
    of ``qblock``): ``(q, scale)``, ``q`` of ``x``'s shape as integers
    held in ``x``'s type, one ``scale`` per block."""
    import jax.numpy as jnp

    blocks = x.reshape(-1, qblock)
    scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1) / 127.0, 1e-12)
    scale = scale.astype(x.dtype)
    q = jnp.clip(jnp.round(blocks / scale[:, None]), -127, 127)
    return q.reshape(x.shape), scale


@functools.lru_cache(maxsize=None)
def _q8_wire(qblock: int):
    """Jitted ``send(tree, residual) -> (the server's view of the tree,
    the new residual)`` on the q8 wire with error feedback.  One per
    block size and process."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def send(tree, residual):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        flat = jnp.concatenate([jnp.ravel(a) for a in leaves])
        d = flat.shape[0]
        x = jnp.pad(flat, (0, -(-d // qblock) * qblock - d)) + residual
        q, scale = quantize_q8(x, qblock)
        seen = (q.reshape(-1, qblock) * scale[:, None]).reshape(x.shape)
        out, at = [], 0
        for a in leaves:
            out.append(seen[at:at + a.size].reshape(a.shape).astype(a.dtype))
            at += a.size
        return jax.tree_util.tree_unflatten(treedef, out), x - seen

    return send


def run(cfg, model, traffic: dict, pop: dict, data: dict, weights,
        rounds: int, *, dtype: str = "float32", precision: str = "highest",
        fault: Optional[str] = None) -> dict:
    """The first ``rounds`` aggregation rounds of one seed.

    Returns ``params`` (the global weights before round 1 and after each
    round, as host pytrees in float32), ``losses`` (the evaluation loss
    after each round), the counts ``uploads``, ``reuploads`` and
    ``adopted``, and ``residuals``: the norm of each client's
    error-feedback residual after the last round, by client id (empty
    off the q8 wire).

    ``fault``: ``"drop_half"`` leaves every second upload of a horizon
    out of the aggregation (the mean is taken over the rest);
    ``"alter_one"`` negates the first upload of every horizon where the
    client produces it; ``"unchanged"`` makes every server step return
    the global weights and statistics it was given; ``"no_adopt"`` has
    every client keep its own weights after an upload, never adopting a
    published global model.  Faults of the q8 wire: ``"no_feedback"``
    keeps each residual but never adds it to the next upload;
    ``"reset_residual"`` sets a client's residual to zero when it adopts
    a global model.
    """
    import jax
    import jax.numpy as jnp

    eng = traffic["engine"]
    dt = jnp.dtype(dtype)
    k = int(eng["k"])
    agg, wire = eng["aggregation"], eng.get("wire", "f32")
    assert agg in ("fedsgd", "fedavg"), agg
    assert wire == "f32" or (wire == "q8" and agg == "fedsgd" and eng.get(
        "error_feedback", True)), (wire, agg, eng.get("error_feedback"))
    upload, server, evaluate = _programs(cfg, model, traffic, dt, precision)
    tree = jax.tree_util.tree_map

    def cast(t):
        """Floating leaves to ``dtype``; integers (token ids) as they are."""
        def leaf(a):
            a = jnp.asarray(a)
            return a.astype(dt) if jnp.issubdtype(a.dtype, jnp.floating) \
                else a
        return tree(leaf, t)

    p0, s0 = cast(weights[0]), cast(weights[1])
    frozen = cast(weights[2]) if len(weights) > 2 else None
    xs, ys = cast(data["xs"]), jnp.asarray(data["ys"])
    tx, ty = cast(data["test_x"]), jnp.asarray(data["test_y"])
    n = pop["n"]
    qb = int(eng.get("quant_block", 512))
    send = _q8_wire(qb) if wire == "q8" else None
    # each client's error-feedback residual, zero before its first upload
    residual, zero = [None] * n, None
    cp, cs = [p0] * n, [s0] * n
    version, uploaded, from_global = [0] * n, [False] * n, [False] * n
    g, gs, t = p0, s0, 0
    out = dict(params=[tree(lambda a: np.asarray(a, np.float32), p0)],
               losses=[], uploads=0, reuploads=0, adopted=0)
    buf = []
    sched = schedule(pop, int(eng["local_epochs"]))
    while t < rounds:
        c = next(sched)
        p_end, s_end, up = upload(cp[c], cs[c], xs[c], ys[c], frozen)
        out["uploads"] += 1
        out["reuploads"] += uploaded[c]
        out["adopted"] += from_global[c]
        uploaded[c] = True
        if fault == "alter_one" and not buf:
            up = tree(lambda a: -a, up)
        if send is not None:
            if zero is None:
                d = sum(a.size for a in jax.tree_util.tree_leaves(up))
                zero = jnp.zeros((-(-d // qb) * qb,), dt)
            carried = zero if residual[c] is None or fault == "no_feedback" \
                else residual[c]
            up, residual[c] = send(up, carried)
        buf.append((up, s_end))
        if version[c] < t and fault != "no_adopt":
            cp[c], cs[c], version[c] = g, gs, t
            from_global[c] = True
            if fault == "reset_residual" and residual[c] is not None:
                residual[c] = zero
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
        out["losses"].append(float(evaluate(g, gs, tx, ty, frozen)))
    out["residuals"] = {c: float(jnp.linalg.norm(r.astype(jnp.float32)))
                        for c, r in enumerate(residual) if r is not None}
    return out
