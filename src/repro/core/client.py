"""On-board local training (ClientUpdate in Algorithms 1-4).

``local_sgd`` runs E epochs of minibatch SGD, optionally with the FedProx
proximal term mu/2 * ||w - w_global||^2. ``local_sgd_clients`` is the round
engine's hot path: a top-level jit of the vmapped trainer, so one cohort of
stacked clients is one compiled dispatch. Its cache is keyed on
(model, batch_size, mu_on, cohort width, data shapes) ONLY — epochs, lr, mu
and the params themselves are dynamic, so a padded fixed-width cohort
compiles exactly once per configuration no matter how per-round eligibility
fluctuates. ``local_sgd_buffer`` trains a FedBuff buffer in one dispatch
at its flush. ``train_cache_sizes`` exposes the jit cache counters so tests
and benchmarks can assert the compile-once invariant.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.models.small import MODELS, xent_loss


def _one_epoch(apply_fn, params, x, y, lr, mu, global_params, batch_size, key):
    n = x.shape[0]
    n_batches = max(n // batch_size, 1)
    perm = jax.random.permutation(key, n)
    xs = x[perm][:n_batches * batch_size].reshape(
        n_batches, batch_size, *x.shape[1:])
    ys = y[perm][:n_batches * batch_size].reshape(n_batches, batch_size)

    def loss(p, xb, yb):
        l = xent_loss(apply_fn, p, xb, yb)
        if global_params is not None:          # FedProx proximal term
            prox = sum(jnp.sum((a - b) ** 2) for a, b in zip(
                jax.tree_util.tree_leaves(p),
                jax.tree_util.tree_leaves(global_params)))
            l = l + 0.5 * mu * prox
        return l

    def body(p, xy):
        xb, yb = xy
        g = jax.grad(loss)(p, xb, yb)
        return jax.tree.map(lambda a, b: a - lr * b, p, g), None

    params, _ = jax.lax.scan(body, params, (xs, ys))
    return params


def _local_sgd(model: str, params, x, y, key, epochs, batch_size: int,
               lr: float, mu: float = 0.0, mu_on: bool = False,
               global_params=None):
    apply_fn = MODELS[model][1]
    gp = global_params if mu_on else None
    epochs = jnp.asarray(epochs, jnp.int32)

    def epoch_body(i, carry):
        p, k = carry
        k, sub = jax.random.split(k)
        p = _one_epoch(apply_fn, p, x, y, lr, mu if mu_on else 0.0, gp,
                       batch_size, sub)
        return (p, k)

    params, _ = jax.lax.fori_loop(0, epochs, epoch_body, (params, key))
    return params


# Train one client for `epochs` epochs (dynamic bound — no recompiles when
# FedProx derives epochs from orbital timing). Returns params.
local_sgd = jax.jit(_local_sgd, static_argnames=("model", "batch_size",
                                                 "mu_on"))


@partial(jax.jit, static_argnames=("model", "batch_size", "mu_on"))
def _local_sgd_batch(model, stacked_params, xs, ys, keys, epochs, batch_size,
                     lr, mu, mu_on, global_params):
    fn = lambda p, x, y, k, e: _local_sgd(model, p, x, y, k, e, batch_size,
                                          lr, mu, mu_on, global_params)
    return jax.vmap(fn)(stacked_params, xs, ys, keys, epochs)


def local_sgd_clients(model, stacked_params, xs, ys, keys, epochs, batch_size,
                      lr, mu=0.0, global_params=None):
    """Train a stacked cohort of clients (W, ...) in one jitted dispatch.

    ``epochs`` may be scalar or per-client (W,) — it is a dynamic argument
    either way, so varying epoch budgets never retrace."""
    mu_on = mu > 0.0
    ep = jnp.broadcast_to(jnp.asarray(epochs, jnp.int32),
                          (jax.tree_util.tree_leaves(xs)[0].shape[0],))
    return _local_sgd_batch(model, stacked_params, xs, ys, keys, ep,
                            batch_size, lr, mu, mu_on, global_params)


@partial(jax.jit, static_argnames=("model", "batch_size"))
def local_sgd_buffer(model, bases, x, y, idx, key, epochs, batch_size, lr,
                     mu):
    """Train a FedBuff buffer of D returns in one dispatch.

    Entry i trains client ``idx[i]`` on ``x[idx[i]]``, ``y[idx[i]]`` (the
    whole fleet's data, gathered inside) for ``epochs[i]`` epochs from
    ``bases[i]``, the version it picked up, with FedProx anchored at that
    base: what ``local_sgd(..., mu_on=True, global_params=bases[i])`` does
    for one return. ``bases`` is a sequence of D models, stacked inside.
    A sequential scan, not a vmap, so every entry keeps the single-client
    program and its own epoch count. Entry i's subkey comes from the
    ``key, sub = split(key)`` chain taken i + 1 times, the keys D
    per-return splits give. Returns (trained models stacked (D, ...), the
    advanced key)."""
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *bases)

    # One dynamic slice per entry, before the scan. The TPU compiler
    # lowers ``x[idx]`` as a gather that copies the whole fleet's data,
    # and hoists a slice taken inside the scan behind a bf16 cast of it.
    def take(a):
        return jnp.stack([jax.lax.dynamic_index_in_dim(a, idx[i], 0, False)
                          for i in range(idx.shape[0])])

    def entry(key, args):
        base, xk, yk, e = args
        key, sub = jax.random.split(key)
        return key, _local_sgd(model, base, xk, yk, sub, e, batch_size, lr,
                               mu, True, base)

    key, trained = jax.lax.scan(entry, key,
                                (stacked, take(x), take(y), epochs))
    return trained, key


def train_cache_sizes() -> dict:
    """Jit-cache entry counts for the training hot paths (trace counters)."""
    return {"local_sgd": local_sgd._cache_size(),
            "local_sgd_clients": _local_sgd_batch._cache_size(),
            "local_sgd_buffer": local_sgd_buffer._cache_size()}


def clear_train_caches() -> None:
    local_sgd._clear_cache()
    _local_sgd_batch._clear_cache()
    local_sgd_buffer._clear_cache()
