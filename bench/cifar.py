"""CIFAR-shaped synthetic data from a key, shared by the image
classifier configurations (``bench/configs/<name>.py`` ``make_data``).

Each class has a smoothed random template; a sample is its class's
template plus per-pixel and per-channel Gaussian noise.  Labels are
uniform over the classes.  Everything is made on the device in one
jitted call.
"""
from __future__ import annotations

import functools


@functools.lru_cache(maxsize=None)
def _data_fn(n_train: int, n_test: int, hw: int, ch: int, n_classes: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        kt, ky, kn, ks, kyt, knt, kst = jax.random.split(key, 7)
        t = jax.random.normal(kt, (n_classes, hw, hw, ch), jnp.float32)
        for _ in range(2):  # low-frequency class templates
            t = (t + jnp.roll(t, 1, 1) + jnp.roll(t, -1, 1)
                 + jnp.roll(t, 1, 2) + jnp.roll(t, -1, 2)) / 5.0
        t = t / jnp.std(t)

        def draw(kyy, knn, kss, n):
            y = jax.random.randint(kyy, (n,), 0, n_classes, jnp.int32)
            x = (t[y] + 0.35 * jax.random.normal(knn, (n, hw, hw, ch))
                 + 0.1 * jax.random.normal(kss, (n, 1, 1, ch)))
            return x.astype(jnp.float32), y

        x, y = draw(ky, kn, ks, n_train)
        xt, yt = draw(kyt, knt, kst, n_test)
        return x, y, xt, yt

    return make


def make_data(cfg: dict, traffic: dict, key) -> dict:
    """``xs``/``ys`` per client as (clients, batches, batch, ...) with one
    label per sample, and the evaluation set, as host arrays."""
    import jax

    pop = traffic["population"]
    n, per = int(pop["n_clients"]), int(pop["samples_per_client"])
    b = int(traffic["engine"]["local_batch_size"])
    assert per % b == 0, (per, b)
    n_eval = int(traffic["eval_samples"])
    assert n * per <= cfg["train_samples"], (n * per, cfg["train_samples"])
    assert n_eval <= cfg["test_samples"], (n_eval, cfg["test_samples"])
    hw, ch = cfg["image_size"], cfg["in_channels"]
    x, y, xt, yt = jax.device_get(
        _data_fn(n * per, n_eval, hw, ch, cfg["n_classes"])(key))
    return dict(xs=x.reshape(n, per // b, b, hw, hw, ch),
                ys=y.reshape(n, per // b, b), test_x=xt, test_y=yt)
