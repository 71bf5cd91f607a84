"""Plain reference of the federated learning the simulator cells run: the
small CNN, local minibatch SGD, QuAFL's per-tensor wire format, the server
mean and test accuracy, in straightforward jax.numpy.

It imports nothing of the program under test. It draws its random numbers
with JAX's threefry keys in the order the configuration file states (one
key per client per round, one permutation per epoch), so that on the same
data the same seed means the same minibatches. Matmuls and convolutions
run at the precision the configuration states (``precision``); ``dtype``
lowers the precision of parameters, data and arithmetic for the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

PRECISION = {"default": lax.Precision.DEFAULT,
             "highest": lax.Precision.HIGHEST}


def init_cnn(key, shape, n_classes, width):
    """Two stride-2 3x3 convolutions (width, 2 * width channels), a
    128-unit dense layer and the classifier; weights drawn N(0, 1/fan_in),
    biases zero."""
    h, w, c = shape
    ks = jax.random.split(key, 4)
    f1, f2 = width, 2 * width
    flat = (h // 4) * (w // 4) * f2
    return {
        "conv1": jax.random.normal(ks[0], (3, 3, c, f1)) * (9 * c) ** -0.5,
        "b1": jnp.zeros((f1,)),
        "conv2": jax.random.normal(ks[1], (3, 3, f1, f2)) * (9 * f1) ** -0.5,
        "b2": jnp.zeros((f2,)),
        "dense": jax.random.normal(ks[2], (flat, 128)) * flat ** -0.5,
        "bd": jnp.zeros((128,)),
        "out": jax.random.normal(ks[3], (128, n_classes)) * 128 ** -0.5,
        "bo": jnp.zeros((n_classes,)),
    }


def apply_cnn(p, x, precision=lax.Precision.DEFAULT):
    dn = ("NHWC", "HWIO", "NHWC")
    h = jax.nn.relu(lax.conv_general_dilated(
        x, p["conv1"], (2, 2), "SAME", dimension_numbers=dn,
        precision=precision) + p["b1"])
    h = jax.nn.relu(lax.conv_general_dilated(
        h, p["conv2"], (2, 2), "SAME", dimension_numbers=dn,
        precision=precision) + p["b2"])
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(jnp.dot(h, p["dense"], precision=precision) + p["bd"])
    return jnp.dot(h, p["out"], precision=precision) + p["bo"]


def xent(p, x, y, precision=lax.Precision.DEFAULT):
    logits = apply_cnn(p, x, precision)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def local_sgd(p, x, y, key, epochs, batch: int, lr: float, mu=0.0,
              anchor=None, precision=lax.Precision.DEFAULT):
    """``epochs`` epochs of plain SGD over one client's samples. Each
    epoch splits the client's key, permutes its samples with the new
    subkey and drops the remainder of the last batch. With an ``anchor``
    the loss adds FedProx's mu / 2 * ||w - anchor||^2."""
    n = x.shape[0]
    nb = max(n // batch, 1)

    def loss(q, xb, yb):
        if anchor is None:
            return xent(q, xb, yb, precision)
        prox = sum(jnp.sum((q[k] - anchor[k]) ** 2) for k in sorted(q))
        return xent(q, xb, yb, precision) + 0.5 * mu * prox

    grad = jax.grad(loss)

    def step(q, xy):
        g = grad(q, xy[0], xy[1])
        return jax.tree.map(lambda a, b: (a - lr * b).astype(a.dtype),
                            q, g), None

    def epoch(_, carry):
        q, k = carry
        k, sub = jax.random.split(k)
        perm = jax.random.permutation(sub, n)
        xs = x[perm][:nb * batch].reshape((nb, batch) + x.shape[1:])
        ys = y[perm][:nb * batch].reshape(nb, batch)
        return lax.scan(step, q, (xs, ys))[0], k

    return lax.fori_loop(0, epochs, epoch, (p, key))[0]


def wire_rows(a, bits: int):
    """What a ``bits``-bit symmetric per-tensor quantized transmission of
    each row of ``a`` delivers: round(x / s) * s, s = max|x| / (2**(bits-1)
    - 1) per row."""
    if not bits:
        return a
    qmax = 2.0 ** (bits - 1) - 1.0
    xf = a.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=tuple(range(1, a.ndim)),
                            keepdims=True), 1e-12) / qmax
    return (jnp.clip(jnp.round(xf / s), -qmax, qmax) * s).astype(a.dtype)


class Replay:
    """Replays one simulation job of the reference from its seed.

    ``data``: (x (K, N, H, W, C), y (K, N), x_test, y_test) device arrays;
    ``fl``: the traffic file's FL settings; ``precision``: the
    configuration's matmul precision, ``default`` or ``highest``.
    ``fedavg``, ``autoflsat`` and ``fedbuff`` take the reference schedule
    and return (final params, accuracy per round, initial params)."""

    def __init__(self, data, fl: dict, width: int, n_classes: int,
                 dtype=jnp.float32, precision: str = "default"):
        self.x, self.y, self.x_test, self.y_test = data
        self.fl, self.width, self.n_classes = fl, width, n_classes
        self.dtype, self.bits = dtype, int(fl.get("quant_bits", 0))
        bs, lr = int(fl["batch_size"]), float(fl["lr"])
        prec = PRECISION[precision]

        def train(p, x, y, key, epochs):
            return local_sgd(p, x.astype(dtype), y, key, epochs, bs, lr,
                             precision=prec)

        self._train = jax.jit(jax.vmap(train, in_axes=(0, 0, 0, 0, None)))
        self._train_prox = jax.jit(
            lambda p, x, y, key, epochs: local_sgd(
                p, x.astype(dtype), y, key, epochs, bs, lr,
                float(fl.get("prox_mu", 0.0)), p, prec))
        self._predict = jax.jit(lambda p, x: jnp.argmax(
            apply_cnn(p, x.astype(dtype), prec), axis=-1))

    def _init(self, seed: int):
        key, init_key = jax.random.split(jax.random.PRNGKey(seed))
        p = init_cnn(init_key, tuple(self.x.shape[2:]), self.n_classes,
                     self.width)
        return key, jax.tree.map(lambda a: a.astype(self.dtype), p)

    def _broadcast(self, p, n: int):
        """The global model as ``n`` clients receive it over the radio."""
        return jax.tree.map(
            lambda a: jnp.broadcast_to(wire_rows(a[None], self.bits)[0],
                                       (n,) + a.shape), p)

    def accuracy(self, p, batch: int = 256) -> float:
        correct = 0
        for i in range(0, self.x_test.shape[0], batch):
            pred = self._predict(p, self.x_test[i:i + batch])
            correct += int(jnp.sum(pred == self.y_test[i:i + batch]))
        return correct / self.x_test.shape[0]

    def fedavg(self, seed: int, rounds):
        """rounds: [(t_start, t_end, participants)]. Each participant
        trains ``epochs`` epochs from the transmitted global model, its
        result crosses the radio back and the server takes the mean."""
        key, p = self._init(seed)
        p0, accs = p, []
        for _, _, sel in rounds:
            ks = jax.random.split(key, len(sel) + 1)
            key = ks[0]
            idx = jnp.asarray(sel)
            out = self._train(self._broadcast(p, len(sel)), self.x[idx],
                              self.y[idx], ks[1:],
                              jnp.int32(self.fl["epochs"]))
            p = jax.tree.map(lambda a: jnp.mean(
                wire_rows(a, self.bits).astype(jnp.float32),
                axis=0).astype(self.dtype), out)
            accs.append(self.accuracy(p))
        return p, accs, p0

    def autoflsat(self, seed: int, rounds, planes: int):
        """rounds: [(t_start, t_end, epochs)]. Every satellite trains from
        the global model, each plane averages its members and the global
        model is the mean of the planes' averages."""
        key, p = self._init(seed)
        p0, accs = p, []
        n = self.x.shape[0]
        for _, _, epochs in rounds:
            ks = jax.random.split(key, n + 1)
            key = ks[0]
            out = self._train(self._broadcast(p, n), self.x, self.y, ks[1:],
                              jnp.int32(epochs))
            p = jax.tree.map(lambda a: jnp.mean(jnp.mean(
                wire_rows(a, self.bits).astype(jnp.float32).reshape(
                    (planes, -1) + a.shape[1:]), axis=1),
                axis=0).astype(self.dtype), out)
            accs.append(self.accuracy(p))
        return p, accs, p0

    def fedbuff(self, seed: int, events, rounds: int):
        """events: [(satellite, epochs, round of pickup)] in the order the
        returns arrive. Each return trains, with FedProx's proximal term,
        from the global model it picked up; every ``buffer_size`` returns
        the global model moves by the mean of the buffered deltas, each
        discounted by (1 + rounds since its pickup) ** -staleness."""
        key, p = self._init(seed)
        p0, accs, picked, buf = p, [], [p], []
        d, a = int(self.fl["buffer_size"]), float(self.fl["staleness_exponent"])
        for k, epochs, r0 in events:
            if len(accs) == rounds:
                break
            key, sub = jax.random.split(key)
            base = picked[r0]
            new = self._train_prox(base, self.x[k], self.y[k], sub,
                                   jnp.int32(epochs))
            buf.append((new, base, (1.0 + len(accs) - r0) ** -a))
            if len(buf) == d:
                f32 = jnp.float32
                deltas = [jax.tree.map(
                    lambda n, b, w=w: jnp.float32(w) * (n.astype(f32)
                                                       - b.astype(f32)),
                    n, b) for n, b, w in buf]
                p = jax.tree.map(lambda g, *ds: (g.astype(f32) + jnp.mean(
                    jnp.stack(ds), axis=0)).astype(self.dtype), p, *deltas)
                picked.append(p)
                buf = []
                accs.append(self.accuracy(p))
        return p, accs, p0
