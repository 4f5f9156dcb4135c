"""Client-side local training for the FL engines (paper §2.1, Eq. 1–3).

Clients run mini-batch SGD (the paper's stated client optimizer) for
``local_epochs`` over their shard.  Both aggregation targets derive from the
same local run:

  * FedAvg uploads the final local weights ``w_i`` (+ non-trainable state,
    e.g. BatchNorm running stats — the extra payload in the paper's Table 2);
  * FedSGD uploads the *cumulative gradient* of the epoch (Eq. 3), which for
    an SGD trajectory equals (w_start − w_end) / lr — the sum of the applied
    mini-batch gradients.  The server then applies Eq. (4)–(5).

The per-client epoch is one jitted ``lax.scan`` over stacked batches with a
validity mask (clients have heterogeneous shard sizes; shards are padded to a
common batch count so one XLA program serves every client).

Uploads leave this module as dense f32 rows (or pytrees on the sequential
path); the engine's wire format (``FLConfig.wire``: f32 | q8 | q4 | topk)
is applied downstream by the :class:`repro.core.flatbuf.PytreeCodec`
quantizer programs, and transmitted-byte accounting for every format lives
in :func:`repro.kernels.quantize.payload_nbytes` — client code is
wire-agnostic by design (the error-feedback residual is engine state, not
client state, so lossy wires never change the local SGD trajectory).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


@dataclasses.dataclass
class ClientState:
    """Host-side record for one simulated client.

    ``speed`` MAY be mutated between (not during) ``FLEngine.run()``
    calls to model drifting device performance: the scheduling
    subsystem snapshots speeds when events are scheduled and rescales
    the compute portion of pending event times on resume
    (:meth:`repro.sched.events.EventQueue.resume`), so a persisted heap
    never replays durations computed from a stale speed."""
    cid: int
    params: Pytree  # current local weights
    model_state: Pytree  # non-trainables (BN running stats)
    version: int  # global round the local model derives from
    n_samples: int
    speed: float  # relative compute speed (samples/sec multiplier)
    comm_time: float  # upload latency (simulated seconds)
    rng: np.random.Generator = None


def sequence_loss(logits, targets, mask=None):
    logz = jax.nn.logsumexp(logits, axis=-1)
    nll = logz - jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def _apply(apply_fn: Callable, params, model_state, x, train: bool,
           frozen):
    """``apply_fn``'s forward pass -> ``(logits, state, *report)``; a
    frozen part of the model, where there is one, goes in as the
    ``frozen`` keyword.  A model may return a third output, a dict of
    counters of the pass (``report``), which evaluation hands on."""
    if frozen is None:
        return apply_fn(params, model_state, x, train)
    return apply_fn(params, model_state, x, train, frozen=frozen)


def make_loss_fn(apply_fn: Callable, kind: str):
    """kind: image | char | sentiment | token.  batch = (x, y, mask).

    ``token``: ``y`` holds one target per position, already shifted (the
    next token sits at its input's position); a sample's mask covers all
    its positions."""

    def loss(params, model_state, x, y, mask, frozen=None):
        logits, new_state, *_ = _apply(apply_fn, params, model_state, x,
                                       True, frozen)
        if kind == "char":
            # next-char prediction: shift by one
            per = sequence_loss(logits[:, :-1], y[:, 1:],
                                mask[:, None] * jnp.ones_like(
                                    y[:, 1:], jnp.float32))
            return per, new_state
        if kind == "token":
            return sequence_loss(logits, y, jnp.broadcast_to(
                mask[:, None], y.shape)), new_state
        per_ex = sequence_loss(logits, y, mask)
        return per_ex, new_state

    return loss


_FN_CACHE: Dict = {}


def _make_epoch_body(apply_fn: Callable, kind: str):
    """Unjitted one-epoch body (the shared core of the sequential and the
    vmapped-batched client paths — identical numerics by construction)."""
    loss_fn = make_loss_fn(apply_fn, kind)
    vg = jax.value_and_grad(loss_fn, has_aux=True)

    def epoch(params, model_state, xs, ys, mask, lr, frozen=None):
        def step(carry, batch):
            p, s = carry
            x, y, m = batch
            (l, s2), g = vg(p, s, x, y, m, frozen)
            any_valid = jnp.sum(m) > 0
            p = jax.tree_util.tree_map(
                lambda a, b: jnp.where(any_valid, a - lr * b, a), p, g)
            s2 = jax.tree_util.tree_map(
                lambda a, b: jnp.where(any_valid, b, a), s, s2)
            return (p, s2), jnp.where(any_valid, l, 0.0)

        (p, s), losses = jax.lax.scan(step, (params, model_state),
                                      (xs, ys, mask))
        n_valid = jnp.maximum(jnp.sum(jnp.any(mask > 0, axis=1)), 1)
        return p, s, jnp.sum(losses) / n_valid

    return epoch


def make_local_train(apply_fn: Callable, kind: str):
    """Returns jitted ``epoch(params, state, xs, ys, mask, lr[, frozen])``.

    xs: (n_batches, B, ...); ys likewise; mask (n_batches, B) marks real
    samples (padding batches have mask 0 and are no-ops).  ``frozen``:
    the model's frozen part, if it has one (an argument, never a
    constant of the program).
    Returns (params', state', mean_loss).

    Memoized on (apply_fn, kind) so multiple engines over the same model
    share one XLA program (jit caches by function identity).
    """
    key = ("train", apply_fn, kind)
    if key in _FN_CACHE:
        return _FN_CACHE[key]
    epoch = jax.jit(_make_epoch_body(apply_fn, kind))
    _FN_CACHE[key] = epoch
    return epoch


def make_batched_local_train(apply_fn: Callable, kind: str,
                             target: str, local_epochs: int,
                             mesh=None):
    """One vmapped XLA program for a whole SFL round of K same-shape
    clients: all K start from the broadcast global model, so only the shard
    data is batched.  Emits the raveled (K, D) flat update buffer directly
    (``target="grad"``: cumulative gradient (w0 - w_end)/lr per Eq. 3;
    ``target="params"``: final local weights), plus the K-stacked final
    model states and per-client losses — no per-client Python dispatch, no
    per-leaf restacking.

    ``mesh`` (a "pod" mesh) pins the K client lanes to the pod axis with
    in-program sharding constraints, so the round runs data-parallel
    across devices and the emitted (K, D) rows land already row-sharded
    for the podwise server reduction.

    Memoized on (apply_fn, kind, target, local_epochs, mesh) so engines
    over the same model share one XLA program.
    """
    key = ("batched", apply_fn, kind, target, local_epochs, mesh)
    if key in _FN_CACHE:
        return _FN_CACHE[key]
    epoch = _make_epoch_body(apply_fn, kind)
    from repro.sharding.flat import constrain_rows

    @jax.jit
    def round_fn(params, model_state, xs_k, ys_k, mask_k, lr, frozen=None):
        xs_k, ys_k, mask_k = constrain_rows((xs_k, ys_k, mask_k), mesh)

        def per_client(xs, ys, mask, frozen):
            p, s = params, model_state
            loss = jnp.float32(0.0)
            for _ in range(local_epochs):
                p, s, loss = epoch(p, s, xs, ys, mask, lr, frozen)
            if target == "grad":
                leaves0 = jax.tree_util.tree_leaves(params)
                leaves1 = jax.tree_util.tree_leaves(p)
                vec = jnp.concatenate(
                    [(jnp.ravel(a).astype(jnp.float32)
                      - jnp.ravel(b).astype(jnp.float32)) / lr
                     for a, b in zip(leaves0, leaves1)])
            else:
                vec = jnp.concatenate(
                    [jnp.ravel(l).astype(jnp.float32)
                     for l in jax.tree_util.tree_leaves(p)])
            return vec, s, loss

        # the frozen part is one unbatched argument of every lane
        vecs, states, losses = jax.vmap(
            per_client, in_axes=(0, 0, 0, None))(xs_k, ys_k, mask_k, frozen)
        return constrain_rows(vecs, mesh), states, losses

    _FN_CACHE[key] = round_fn
    return round_fn


def _codec_key(codec) -> tuple:
    """Hashable static layout of a PytreeCodec — programs built over one
    layout are shared by every codec instance with the same layout."""
    return (codec.treedef, tuple(codec.shapes),
            tuple(str(d) for d in codec.dtypes), codec.qblock)


def model_has_conv(apply_fn: Callable, params: Pytree, model_state: Pytree,
                   sample_x, frozen: Pytree = None) -> bool:
    """True iff ``apply_fn``'s forward pass traces a convolution.

    The heterogeneous-params vmap lowers convolutions to *grouped*
    convolutions (one group per lane), which XLA CPU executes worse than
    per-client dispatch (ROADMAP: 0.4-0.6x for the 16x16 CNN) — the
    signal ``wave_impl="auto"`` uses to pick the ``lax.map`` serial-wave
    fallback on CPU hosts.  Cached per apply_fn (one abstract trace)."""
    key = ("hasconv", apply_fn)
    if key in _FN_CACHE:
        return _FN_CACHE[key]
    try:
        jaxpr = jax.make_jaxpr(
            lambda p, s, x, f: _apply(apply_fn, p, s, x, True, f))(
                params, model_state, sample_x, frozen)
        has = "conv_general_dilated" in str(jaxpr)
    except Exception:  # unusual apply signature: assume the common case
        has = False
    _FN_CACHE[key] = has
    return has


def resolve_wave_impl(impl: str, apply_fn: Callable, params: Pytree,
                      model_state: Pytree, sample_x,
                      frozen: Pytree = None) -> str:
    """Resolve ``FLConfig.wave_impl``: "vmap" / "map" pass through;
    "auto" keeps the vmapped wave except for conv models on CPU, where
    the grouped-convolution lowering loses to one serial-wave dispatch
    (identical numerics either way — lanes are independent)."""
    assert impl in ("vmap", "map", "auto"), impl
    if impl != "auto":
        return impl
    if jax.default_backend() != "cpu":
        return "vmap"  # grouped convs are native on TPU/GPU
    return ("map" if model_has_conv(apply_fn, params, model_state,
                                    sample_x, frozen) else "vmap")


def make_batched_hetero_train(apply_fn: Callable, kind: str, target: str,
                              local_epochs: int, codec,
                              impl: str = "vmap", mesh=None):
    """One XLA program for a whole SAFL horizon wave of K clients
    with *heterogeneous* parameters.

    Unlike :func:`make_batched_local_train` (SFL: all K clients start from
    the one broadcast global model, so only shard data is batched), the
    semi-async schedule leaves every client on its own weights — so params
    are batched too, carried as flat (K, D) f32 rows
    (:class:`repro.core.flatbuf.PytreeCodec` layout).  Each vmapped lane
    unravels its row to the model pytree, runs ``local_epochs`` of the
    shared epoch body (identical numerics to the sequential path by
    construction), and re-ravels, emitting:

      * ``vecs`` (K, D): the upload rows — cumulative gradient
        (row_start - row_end)/lr for ``target="grad"`` (Eq. 3), the final
        local weights for ``target="params"``;
      * ``new_flat`` (K, D): the final local weights as flat rows (the
        clients' carried state for the next upload period);
      * the K-stacked final model states and per-client mean losses
        (device scalars — never fetched in the hot loop).

    The wave's shard data is *gathered inside the program*: callers pass
    the engine's device-resident (n_clients, ...) shard bank plus the
    (K,) client-index vector, so a wave is one dispatch with no separate
    gather ops.  Memoized on (apply_fn, kind, target, local_epochs, codec
    layout, impl, mesh); K is a static shape, so each distinct wave size
    compiles once and is cached (wave sizes are bounded by the buffer
    size K, and power-of-two bucketed to O(log K) distinct programs by
    the engine under ``FLConfig.wave_buckets``).

    ``impl`` selects the lane execution: ``"vmap"`` (one vectorized
    program — the parallel-hardware fast path) or ``"map"`` (``lax.map``:
    still ONE dispatch for the whole wave, but lanes run serially inside
    it — identical numerics, and it sidesteps the grouped-convolution
    lowering the vmapped form pays for conv models on CPU).  ``mesh``
    (a "pod" mesh) pins the vmapped lanes and the emitted (K, D) rows to
    the pod axis in-program, so the wave trains data-parallel across
    devices (ignored for ``impl="map"`` — a serial wave has no lane
    parallelism to shard).
    """
    assert impl in ("vmap", "map"), impl
    if impl == "map":
        mesh = None
    key = ("hetero", apply_fn, kind, target, local_epochs,
           _codec_key(codec), impl, mesh)
    if key in _FN_CACHE:
        return _FN_CACHE[key]
    epoch = _make_epoch_body(apply_fn, kind)
    unravel, ravel = codec.unravel_fn, codec.ravel_fn
    from repro.sharding.flat import constrain_rows

    def per_client(flat, state, xs, ys, mask, lr, frozen):
        p, s = unravel(flat), state
        loss = jnp.float32(0.0)
        for _ in range(local_epochs):
            p, s, loss = epoch(p, s, xs, ys, mask, lr, frozen)
        new_flat = ravel(p)
        if target == "grad":
            vec = (flat - new_flat) / lr
        else:
            vec = new_flat
        return vec, new_flat, s, loss

    @jax.jit
    def round_fn(flat_k, states_k, xs_all, ys_all, mask_all, idx, lr,
                 frozen=None):
        lanes = (flat_k, states_k, xs_all[idx], ys_all[idx], mask_all[idx])
        if impl == "map":
            return jax.lax.map(lambda a: per_client(*a, lr, frozen), lanes)
        lanes = constrain_rows(lanes, mesh)
        # the frozen part is one unbatched argument of every lane: never
        # broadcast per lane, never a constant of the program
        vecs, new_flat, states, losses = jax.vmap(
            lambda f, st, x, y, m, fr: per_client(f, st, x, y, m, lr, fr),
            in_axes=(0, 0, 0, 0, 0, None))(*lanes, frozen)
        # only the upload rows stay pod-sharded (they feed the sharded
        # buffer scatter + podwise reduction); new_flat is host-side
        # client state, indexed row-wise at refresh — pinning it would
        # turn every refresh into a cross-device gather
        vecs = constrain_rows(vecs, mesh)
        return vecs, new_flat, states, losses

    _FN_CACHE[key] = round_fn
    return round_fn


@functools.lru_cache(maxsize=None)
def _state_stacker(n: int):
    """One-dispatch stack of n trees, leaf by leaf: a wave's start rows
    with their model states (``jnp.stack`` outside jit is an expand_dims
    per operand + concat, n + 1 dispatches for every leaf)."""
    return jax.jit(lambda *trees: jax.tree_util.tree_map(
        lambda *ls: jnp.stack(ls), *trees))


@jax.jit
def _take_states(tree: Pytree, idx: jax.Array) -> Pytree:
    return jax.tree_util.tree_map(lambda l: l[idx], tree)


def stack_states(trees) -> Pytree:
    """``tree_map(lambda *ls: jnp.stack(ls), *trees)`` as one program for
    the whole tree (a lane's ``(row, state)`` pair, or a state alone); an
    empty tree (a model without BatchNorm) comes back as it is, with
    nothing dispatched."""
    if not jax.tree_util.tree_leaves(trees[0]):
        return trees[0]
    return _state_stacker(len(trees))(*trees)


@functools.partial(jax.jit, static_argnums=1)
def broadcast_states(tree: Pytree, n: int) -> Pytree:
    """n lanes of one tree (the global ``(row, state)``) as one program: a
    broadcast, where a stack of n copies would also hold an n-lane temp
    on the TPU."""
    return jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l, (n,) + l.shape), tree)


def take_states(tree: Pytree, idx) -> Pytree:
    """``tree_map(lambda l: l[idx], tree)`` for a vector of rows ``idx``,
    as one program for the whole tree (a state tree, or a ``(rows,
    states)`` pair).  ``idx`` is a traced argument, so other rows of the
    same count reuse the compiled program; an empty tree comes back as
    it is, with nothing dispatched."""
    if not jax.tree_util.tree_leaves(tree):
        return tree
    return _take_states(tree, np.asarray(idx, np.int32))


@jax.jit
def _split_states(tree: Pytree, idx: jax.Array) -> tuple:
    return tuple(jax.tree_util.tree_map(lambda l: l[idx[i]], tree)
                 for i in range(idx.shape[0]))


def split_states(tree: Pytree, rows) -> list:
    """``[tree_map(lambda l: l[row], tree) for row in rows]`` as one
    program: one tree per row, the rows a traced argument (a new count
    of rows compiles, new row values do not); an empty tree comes back
    as it is, with nothing dispatched."""
    if not rows or not jax.tree_util.tree_leaves(tree):
        return [tree] * len(rows)
    return list(_split_states(tree, np.asarray(rows, np.int32)))


def cumulative_gradient(w_start: Pytree, w_end: Pytree, lr: float) -> Pytree:
    """FedSGD upload payload: sum of applied mini-batch gradients (Eq. 3)."""
    return jax.tree_util.tree_map(
        lambda a, b: (a - b) / lr, w_start, w_end)


def _make_eval_body(apply_fn: Callable, kind: str):
    """``evaluate(params, state, x, y[, frozen]) -> (accuracy, loss,
    *report)``; for ``token`` both are over every position.  ``report``:
    the counters dict a model returns as its third output, if it does."""
    def evaluate(params, model_state, x, y, frozen=None):
        logits, _, *report = _apply(apply_fn, params, model_state, x, False,
                                    frozen)
        if kind == "char":
            pred = jnp.argmax(logits[:, :-1], axis=-1)
            tgt = y[:, 1:]
            acc = jnp.mean((pred == tgt).astype(jnp.float32))
            loss = sequence_loss(logits[:, :-1], tgt)
        else:
            pred = jnp.argmax(logits, axis=-1)
            acc = jnp.mean((pred == y).astype(jnp.float32))
            loss = sequence_loss(logits, y)
        return (acc, loss, *report)

    return evaluate


def make_eval_fn(apply_fn: Callable, kind: str):
    key = ("eval", apply_fn, kind)
    if key in _FN_CACHE:
        return _FN_CACHE[key]
    evaluate = jax.jit(_make_eval_body(apply_fn, kind))
    _FN_CACHE[key] = evaluate
    return evaluate


def make_flat_eval_fn(apply_fn: Callable, kind: str, codec):
    """``evaluate(flat_params, state, x, y[, frozen])`` with the unravel
    fused into the jitted program — the batched engine keeps the global
    model as a flat (D,) row end-to-end and never materializes the
    pytree per eval."""
    key = ("eval_flat", apply_fn, kind, _codec_key(codec))
    if key in _FN_CACHE:
        return _FN_CACHE[key]
    body = _make_eval_body(apply_fn, kind)
    unravel = codec.unravel_fn
    evaluate = jax.jit(
        lambda flat, state, x, y, frozen=None: body(unravel(flat), state, x,
                                                    y, frozen))
    _FN_CACHE[key] = evaluate
    return evaluate


def pytree_bytes(tree: Pytree) -> int:
    return sum(np.prod(l.shape) * jnp.dtype(l.dtype).itemsize
               for l in jax.tree_util.tree_leaves(tree))
