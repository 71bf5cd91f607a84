"""The benchmark's federated dataset, made on the device from a seed.

Class-conditional Gaussian images around low-frequency per-class
prototypes, with a Dirichlet(alpha) label skew across clients: the
synthetic EuroSAT-shaped federated split the simulator cells train on. It
is the benchmark's own copy, so no change to the program moves the
yardstick; the program receives the arrays.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class FedData:
    x: jax.Array          # (K, N, H, W, C) per-client images
    y: jax.Array          # (K, N) int32 labels
    x_test: jax.Array     # (M, H, W, C)
    y_test: jax.Array     # (M,)
    n_classes: int

    @property
    def n_per_client(self) -> int:
        return self.x.shape[1]

    def arrays(self):
        return self.x, self.y, self.x_test, self.y_test


def _class_means(key, n_classes, shape, scale):
    h, w, c = shape
    kf, kp = jax.random.split(key)
    freqs = jax.random.normal(kf, (n_classes, 4, c)) * scale
    phases = jax.random.uniform(kp, (n_classes, 4, c)) * 2 * jnp.pi
    yy = jnp.linspace(0, 2 * jnp.pi, h)[None, :, None, None]
    xx = jnp.linspace(0, 2 * jnp.pi, w)[None, None, :, None]
    f = freqs[:, :, None, None, :]
    ph = phases[:, :, None, None, :]
    return (f[:, 0] * jnp.sin(yy + ph[:, 0]) + f[:, 1] * jnp.cos(xx + ph[:, 1])
            + f[:, 2] * jnp.sin(2 * yy + xx + ph[:, 2])
            + f[:, 3] * jnp.cos(yy - 2 * xx + ph[:, 3]))


@partial(jax.jit, static_argnames=("clients", "per_client", "n_test",
                                   "shape", "n_classes", "alpha", "noise"))
def _make(key, clients, per_client, n_test, shape, n_classes, alpha, noise):
    km, kl, kx, kt, ky = jax.random.split(key, 5)
    means = _class_means(km, n_classes, shape, 2.0)
    kp, ks = jax.random.split(kl)
    probs = jax.random.dirichlet(kp, jnp.full((n_classes,), alpha),
                                 (clients,))
    y = jax.vmap(lambda k, p: jax.random.choice(
        k, n_classes, (per_client,), p=p))(
            jax.random.split(ks, clients), probs).astype(jnp.int32)
    x = means[y] + noise * jax.random.normal(kx, y.shape + shape)
    y_test = jax.random.randint(ky, (n_test,), 0, n_classes, dtype=jnp.int32)
    x_test = means[y_test] + noise * jax.random.normal(kt, y_test.shape + shape)
    return x, y, x_test, y_test


def make(spec: dict, clients: int, seed: int) -> FedData:
    """``spec``: the configuration's ``data`` entry (image shape, classes,
    samples per client, test samples, Dirichlet alpha, noise)."""
    shape = tuple(spec["image_shape"])
    x, y, xt, yt = _make(jax.random.PRNGKey(seed), clients,
                         spec["per_client"], spec["n_test"], shape,
                         spec["n_classes"], float(spec["alpha"]),
                         float(spec["noise"]))
    return FedData(x, y, xt, yt, spec["n_classes"])
