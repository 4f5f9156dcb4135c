"""A tiny token configuration for the tests of the configuration
protocol (``bench/harness.py``): a frozen embedding and output head with
a trained low-rank adapter between them, next-token targets, and ids
above 256 (which bfloat16 cannot hold exactly).  It is no cell of the
benchmark."""
from __future__ import annotations


def make_data(cfg, traffic, key):
    """Uniform random token sequences; ``ys`` is ``xs`` shifted by one
    position (the next token), both (clients, batches, batch, seq_len)
    int32."""
    import jax
    import jax.numpy as jnp

    pop = traffic["population"]
    n, per = int(pop["n_clients"]), int(pop["samples_per_client"])
    b = int(traffic["engine"]["local_batch_size"])
    n_eval, t = int(traffic["eval_samples"]), int(cfg["seq_len"])
    k1, k2 = jax.random.split(key)
    seq = jax.random.randint(k1, (n * per, t + 1), 0, cfg["vocab_size"],
                             jnp.int32)
    test = jax.random.randint(k2, (n_eval, t + 1), 0, cfg["vocab_size"],
                              jnp.int32)
    seq, test = jax.device_get((seq, test))
    return dict(xs=seq[:, :-1].reshape(n, per // b, b, t),
                ys=seq[:, 1:].reshape(n, per // b, b, t),
                test_x=test[:, :-1], test_y=test[:, 1:])


def init(cfg, key):
    """(params, state, frozen): the adapter ``a`` (hidden x rank) and
    ``b`` (rank x hidden) trained, no state, the embedding and the head
    frozen."""
    import jax
    import jax.numpy as jnp

    v, h, r = cfg["vocab_size"], cfg["hidden_size"], cfg["adapter_rank"]
    ke, kh, ka, kb = jax.random.split(key, 4)
    frozen = {"embed": jax.random.normal(ke, (v, h), jnp.float32),
              "head": jax.random.normal(kh, (h, v), jnp.float32) / h ** 0.5}
    params = {"a": 0.3 * jax.random.normal(ka, (h, r), jnp.float32),
              "b": 0.3 * jax.random.normal(kb, (r, h), jnp.float32)}
    return params, {}, frozen


def apply(cfg, params, state, x, train, frozen=None):
    """Logits (..., seq_len, vocab_size) of ids ``x``."""
    del train
    h = frozen["embed"][x]
    h = h + (h @ params["a"]) @ params["b"]
    return h @ frozen["head"], state


def flops_forward(cfg):
    """Per sample: the adapter's two matmuls and the head, at every
    position (the embedding is a gather)."""
    h, r = cfg["hidden_size"], cfg["adapter_rank"]
    return float(cfg["seq_len"] * (2 * 2 * h * r + 2 * h * cfg["vocab_size"]))


def flops_train(cfg):
    """Per sample: the adapter forward, input and weight gradients (three
    passes); the frozen head forward and input gradient (two)."""
    h, r = cfg["hidden_size"], cfg["adapter_rank"]
    return float(cfg["seq_len"] * (3 * 2 * 2 * h * r
                                   + 2 * 2 * h * cfg["vocab_size"]))
