"""Small on-board models for the FLySTacK simulator (paper trains LeNet5 /
MobileNetV2 / ResNet18-class models on CubeSat hardware; we provide a LeNet5
equivalent CNN and an MLP, pure JAX, vmappable across satellite clients)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs


def init_cnn(key, input_shape, n_classes, width=16):
    h, w, c = input_shape
    ks = jax.random.split(key, 4)
    f1, f2 = width, width * 2
    # two stride-2 conv blocks then dense
    h2, w2 = h // 4, w // 4
    return {
        "conv1": jax.random.normal(ks[0], (3, 3, c, f1)) * (9 * c) ** -0.5,
        "b1": jnp.zeros((f1,)),
        "conv2": jax.random.normal(ks[1], (3, 3, f1, f2)) * (9 * f1) ** -0.5,
        "b2": jnp.zeros((f2,)),
        "dense": jax.random.normal(ks[2], (h2 * w2 * f2, 128))
        * (h2 * w2 * f2) ** -0.5,
        "bd": jnp.zeros((128,)),
        "out": jax.random.normal(ks[3], (128, n_classes)) * 128 ** -0.5,
        "bo": jnp.zeros((n_classes,)),
    }


def apply_cnn(params, x):
    """x (B, H, W, C) -> logits (B, n_classes)."""
    dn = ("NHWC", "HWIO", "NHWC")
    h = lax.conv_general_dilated(x, params["conv1"], (2, 2), "SAME",
                                 dimension_numbers=dn) + params["b1"]
    h = jax.nn.relu(h)
    h = lax.conv_general_dilated(h, params["conv2"], (2, 2), "SAME",
                                 dimension_numbers=dn) + params["b2"]
    h = jax.nn.relu(h)
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ params["dense"] + params["bd"])
    return h @ params["out"] + params["bo"]


def init_mlp(key, input_shape, n_classes, hidden=128):
    h, w, c = input_shape
    d = h * w * c
    ks = jax.random.split(key, 2)
    return {
        "w1": jax.random.normal(ks[0], (d, hidden)) * d ** -0.5,
        "b1": jnp.zeros((hidden,)),
        "w2": jax.random.normal(ks[1], (hidden, n_classes)) * hidden ** -0.5,
        "b2": jnp.zeros((n_classes,)),
    }


def apply_mlp(params, x):
    h = x.reshape(x.shape[0], -1)
    h = jax.nn.relu(h @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


MODELS = {"cnn": (init_cnn, apply_cnn), "mlp": (init_mlp, apply_mlp)}


def model_bytes(params, bits=32):
    n = sum(p.size for p in jax.tree_util.tree_leaves(params))
    return n * bits / 8


def xent_loss(apply_fn, params, x, y):
    logits = apply_fn(params, x)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return (logz - gold).mean()


_ACC_FNS = {}


def _accuracy_fn(apply_fn, batch):
    """One jitted correct-count program per (apply_fn, batch): the eval set
    is padded to a whole number of batches inside the trace and scanned on
    device, so evaluation is a single dispatch + a single host sync instead
    of one round-trip per 256 samples."""
    fn = _ACC_FNS.get((apply_fn, batch))
    if fn is None:
        @jax.jit
        def fn(params, x, y):
            n = x.shape[0]
            nb = -(-n // batch)
            pad = nb * batch - n
            xb = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
                (nb, batch) + x.shape[1:])
            yb = jnp.pad(y, (0, pad)).reshape(nb, batch)
            mb = (jnp.arange(nb * batch) < n).reshape(nb, batch)

            def body(c, xym):
                xi, yi, mi = xym
                pred = apply_fn(params, xi).argmax(-1)
                return c + jnp.sum((pred == yi) & mi), None

            c, _ = lax.scan(body, jnp.zeros((), jnp.int32), (xb, yb, mb))
            return c
        _ACC_FNS[(apply_fn, batch)] = fn
    return fn


def accuracy(apply_fn, params, x, y, batch=256):
    return int(obs.sync(_accuracy_fn(apply_fn, batch)(params, x, y))) \
        / x.shape[0]
