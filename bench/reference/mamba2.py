"""The plain reference of the LM cell: one tier-2 round of two-pod Mamba-2
language-model training, in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, written from the Mamba-2
paper (Dao & Gu, "Transformers are SSMs", arXiv:2405.21060) and nothing of
the program.

Per pod and step: embedding; per layer an RMSNorm, the in-projection to
z, x, B, C and dt, the causal depthwise convolution and SiLU over (x, B,
C), dt = softplus(dt + dt_bias), the SSD, that is the recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t

(heads share B and C within a group), the gated RMSNorm
rmsnorm(y * silu(z)), the out-projection and the residual; the final
RMSNorm, the tied output head and the mean cross-entropy of the next
token. Then AdamW on every weight, and after the round the tier-2 mean of
both pods' weights and both moments.

Departures from the paper, each one the deployment's choice or the
reference's:

- The SSD is summed in its quadratic form over the whole sequence (the
  paper's masked matrix M = L o C B^T, section 3): the recurrence
  unrolled, with no chunks and no state passed between them, so it shares
  nothing with the chunked algorithm it checks. Run token by token, the
  recurrence read and wrote the 2 MB state of every layer for each of
  2,048 tokens and took 241 s a round on four TPU v5 lite chips; the
  tests hold this form to the token-by-token recurrence.
- Every matrix multiplication is float32 at highest precision; the
  deployment computes in bfloat16.
- dt is not clamped (the published code's limit is (0, inf)).
- Weights are a seeded draw (``layer_weights``), not a checkpoint.
- AdamW: decoupled weight decay on every weight, gradients clipped to a
  global norm, and a linear warm-up lr * min(1, (t + 1) / warmup) at the
  t-th update (t from 1), no decay, as the traffic's hyper-parameters
  state.

At published widths one pod's weights, gradients and moments take 21.5
GB, more than a chip holds, so each pod runs on two devices: weights,
gradients and activations on one, both moments on the other. The forward
pass keeps each layer's input; the backward pass recomputes one layer at
a time under ``jax.vjp``; AdamW updates one leaf at a time. ``bits``, where
given, rounds the operands of the matrix multiplications (and their
cotangents) to that many mantissa bits: the control of the comparison.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

#: Heads whose (L, L) decay blocks the SSD holds at a time.
HEAD_GROUP = 16


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d_model: int
    n_layers: int
    d_state: int
    head_dim: int
    expand: int
    n_groups: int
    conv_width: int
    norm_eps: float
    dt_min: float
    dt_max: float

    @classmethod
    def of(cls, model: dict) -> "Dims":
        return cls(**{f.name: model[f.name] for f in dataclasses.fields(cls)})

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def heads(self) -> int:
        return self.d_inner // self.head_dim


# ---------------------------------------------------------------------------
# weights from a seed
# ---------------------------------------------------------------------------


def embed_weights(key, d: Dims):
    return jax.random.normal(jax.random.fold_in(key, 0),
                             (d.vocab, d.d_model), jnp.float32) * 0.02


def layer_key(key, i):
    return jax.random.fold_in(key, i + 1)


def layer_weights(key, d: Dims) -> dict:
    """One layer's weights: projections at 1/sqrt(fan-in) (the
    out-projection further by 1/sqrt(layers)), A in [1, 16] and dt in
    [dt_min, dt_max] log-uniformly as in the paper's code, unit norms."""
    ks = jax.random.split(key, 6)
    gn = d.n_groups * d.d_state
    conv_ch = d.d_inner + 2 * gn
    normal = lambda k, shape, std: jax.random.normal(k, shape) * std
    u = jax.random.uniform(ks[3], (d.heads,))
    dt = jnp.exp(u * (np.log(d.dt_max) - np.log(d.dt_min)) + np.log(d.dt_min))
    return {
        "norm": jnp.ones((d.d_model,)),
        "in_proj": normal(ks[0], (d.d_model, 2 * d.d_inner + 2 * gn + d.heads),
                          d.d_model ** -0.5),
        "conv_w": normal(ks[1], (d.conv_width, conv_ch), d.conv_width ** -0.5),
        "conv_b": normal(ks[4], (conv_ch,), 0.1),
        "A_log": jnp.log(jax.random.uniform(ks[2], (d.heads,), minval=1.0,
                                            maxval=16.0)),
        "D": jnp.ones((d.heads,)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "gate_norm": jnp.ones((d.d_inner,)),
        "out_proj": normal(ks[5], (d.d_inner, d.d_model),
                           (d.d_inner * d.n_layers) ** -0.5),
    }


def weights(seed: int, d: Dims, device) -> dict:
    """The weights of ``seed`` on ``device``, built one layer at a time."""
    one_layer = jax.jit(lambda k, i: layer_weights(layer_key(k, i), d))
    with jax.default_device(device):
        key = jax.random.PRNGKey(seed)
        return {"embed": jax.jit(partial(embed_weights, d=d))(key),
                "layers": [one_layer(key, i) for i in range(d.n_layers)],
                "norm": jnp.ones((d.d_model,))}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _round_mantissa(x, bits: int):
    """``x`` rounded to nearest (ties to even) at ``bits`` mantissa bits,
    keeping float32's exponent range."""
    drop = 23 - bits
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    lsb = (u >> drop) & jnp.uint32(1)
    u = (u + jnp.uint32((1 << (drop - 1)) - 1) + lsb) \
        & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def round_operand(x, bits: int):
    """A matmul operand at ``bits`` mantissa bits; its cotangent too."""
    return _round_mantissa(x, bits)


round_operand.defvjp(lambda x, bits: (_round_mantissa(x, bits), None),
                     lambda bits, _, g: (_round_mantissa(g, bits),))


def _mm(a, b, bits):
    if bits is not None:
        a, b = round_operand(a, bits), round_operand(b, bits)
    return a @ b


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def causal_conv(x, w, b):
    """Depthwise causal convolution over (B, L, CH) with taps (W, CH):
    out_t = b + sum_i w_i x_{t - W + 1 + i}."""
    width, length = w.shape[0], x.shape[1]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return b + sum(pad[:, i:i + length] * w[i] for i in range(width))


def ssd(x, dt, A, B, C):
    """The SSD of one sequence in its quadratic (masked) form: x (L, H,
    P), dt (L, H), A (H,), B and C (L, H, N) -> y (L, H, P) with

        y_t = sum_{s <= t} (C_t . B_s) exp(dt_{s+1} A + ... + dt_t A) dt_s x_s,

    the per-token recurrence's sum written out, over the whole sequence.
    Heads go ``HEAD_GROUP`` at a time, each group recomputed when
    differentiated, so that one (heads, L, L) block is held at a time."""
    length, heads, _ = x.shape
    cum = jnp.cumsum(dt * A, axis=0).T                      # (H, L)
    causal = jnp.tril(jnp.ones((length, length), bool))

    @jax.checkpoint
    def group(x, dt, cum, B, C):
        decay = jnp.exp(jnp.where(causal, cum[:, :, None] - cum[:, None, :],
                                  -jnp.inf))                # (h, t, s)
        w = jnp.einsum("thn,shn->hts", C, B) * decay * dt.T[:, None, :]
        return jnp.einsum("hts,shp->thp", w, x)

    return jnp.concatenate([
        group(x[:, g:g + HEAD_GROUP], dt[:, g:g + HEAD_GROUP],
              cum[g:g + HEAD_GROUP], B[:, g:g + HEAD_GROUP],
              C[:, g:g + HEAD_GROUP])
        for g in range(0, heads, HEAD_GROUP)], axis=1)


def layer(p, h, d: Dims, bits=None):
    """One Mamba-2 block with its residual: h (B, L, D) -> (B, L, D)."""
    b, length, _ = h.shape
    gn = d.n_groups * d.d_state
    zxbcdt = _mm(rmsnorm(h, p["norm"], d.norm_eps), p["in_proj"], bits)
    z = zxbcdt[..., :d.d_inner]
    xbc = zxbcdt[..., d.d_inner:2 * d.d_inner + 2 * gn]
    dt = jax.nn.softplus(zxbcdt[..., 2 * d.d_inner + 2 * gn:] + p["dt_bias"])
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    x = xbc[..., :d.d_inner].reshape(b, length, d.heads, d.head_dim)
    group = np.arange(d.heads) // (d.heads // d.n_groups)
    B = xbc[..., d.d_inner:d.d_inner + gn].reshape(
        b, length, d.n_groups, d.d_state)[:, :, group]
    C = xbc[..., d.d_inner + gn:].reshape(
        b, length, d.n_groups, d.d_state)[:, :, group]
    A = -jnp.exp(p["A_log"])
    y = jax.vmap(ssd, in_axes=(0, 0, None, 0, 0))(x, dt, A, B, C)
    y = (y + p["D"][:, None] * x).reshape(b, length, d.d_inner)
    y = rmsnorm(y * jax.nn.silu(z), p["gate_norm"], d.norm_eps)
    return h + _mm(y, p["out_proj"], bits)


def head_loss(embed, norm, h, labels, d: Dims, bits=None):
    """Mean next-token cross-entropy of the tied head over labels >= 0."""
    logits = _mm(rmsnorm(h, norm, d.norm_eps), embed.T, bits)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    nll = (jax.nn.logsumexp(logits, axis=-1) - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


def stacked_loss(embed, layers, norm, tokens, labels, d: Dims):
    """The loss of the weights whose layers are stacked on a leading axis
    (each leaf of ``layers`` (n_layers, ...)), forward only: the
    reference's loss at weights it did not train itself."""
    h, _ = jax.lax.scan(lambda h, p: (layer(p, h, d), None), embed[tokens],
                        layers)
    return head_loss(embed, norm, h, labels, d)


# ---------------------------------------------------------------------------
# training, block by block
# ---------------------------------------------------------------------------


class Programs:
    """The reference's jitted pieces for ``Dims`` ``d``; ``bits`` rounds
    the matmul operands (None: float32)."""

    def __init__(self, d: Dims, hp: dict, bits=None):
        self.d, self.hp = d, hp
        f = partial(layer, d=d, bits=bits)
        self.forward = jax.jit(f)
        self.backward = jax.jit(lambda p, h, g: jax.vjp(f, p, h)[1](g))
        self.head = jax.jit(jax.value_and_grad(
            partial(head_loss, d=d, bits=bits), argnums=(0, 1, 2)))
        self.head_value = jax.jit(partial(head_loss, d=d, bits=bits))
        self.embed = jax.jit(lambda e, tokens: e[tokens])
        self.embed_grad = jax.jit(lambda g, tokens, gh: g.at[tokens].add(gh))
        self.sq_norm = jax.jit(lambda tree: sum(
            jnp.sum(x * x) for x in jax.tree.leaves(tree)))
        self.clip = jax.jit(lambda sq: jnp.minimum(
            1.0, hp["grad_clip"] / jnp.maximum(jnp.sqrt(sq), 1e-9)))
        self.adamw = jax.jit(self._adamw, donate_argnums=(2, 3))
        self.mean = jax.jit(lambda *xs: sum(xs) / len(xs), donate_argnums=0)

    def _adamw(self, p, g, m, v, scale, t):
        hp = self.hp
        b1, b2 = hp["b1"], hp["b2"]
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        lr = hp["lr"] * jnp.minimum(1.0, (t + 1) / max(hp["warmup_steps"], 1))
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        return p - lr * (mhat / (jnp.sqrt(vhat) + hp["eps"])
                         + hp["weight_decay"] * p), m, v

    def loss(self, w, tokens, labels):
        """The loss of the weights ``w`` on one batch, forward only, one
        layer at a time."""
        h = self.embed(w["embed"], tokens)
        for p in w["layers"]:
            h = self.forward(p, h)
        return self.head_value(w["embed"], w["norm"], h, labels)

    def grads(self, w, tokens, labels):
        """(loss, gradients) of the weights ``w`` on one batch, layer by
        layer: the forward pass keeps each layer's input, the backward
        pass recomputes one layer at a time."""
        hs = [self.embed(w["embed"], tokens)]
        for p in w["layers"]:
            hs.append(self.forward(p, hs[-1]))
        loss, (g_embed, g_norm, gh) = self.head(w["embed"], w["norm"],
                                                hs.pop(), labels)
        g_layers = [None] * len(w["layers"])
        for i in reversed(range(len(w["layers"]))):
            g_layers[i], gh = self.backward(w["layers"][i], hs.pop(), gh)
        return loss, {"embed": self.embed_grad(g_embed, tokens, gh),
                      "layers": g_layers, "norm": g_norm}


class Pod:
    """One pod's replica: weights (and gradients, activations) on
    ``devices[0]``, the AdamW moments on ``devices[1]``; each a list of
    leaves, replaced one at a time so that no whole second copy is
    held."""

    def __init__(self, progs: Programs, w: dict, devices):
        self.progs = progs
        self.dw, self.dm = devices
        self.w, self.tree = jax.tree.flatten(w)
        with jax.default_device(self.dm):
            self.m = [jnp.zeros(x.shape) for x in self.w]
            self.v = [jnp.zeros(x.shape) for x in self.w]
        self.t = 0

    def weights(self) -> dict:
        return jax.tree.unflatten(self.tree, self.w)

    def step(self, tokens, labels):
        """One AdamW step on one batch; returns the loss (on the device)."""
        progs, dw, dm = self.progs, self.dw, self.dm
        loss, g = progs.grads(self.weights(), jax.device_put(tokens, dw),
                              jax.device_put(labels, dw))
        g = jax.tree.leaves(g)
        scale = jax.device_put(progs.clip(progs.sq_norm(g)), dm)
        self.t += 1
        t = jnp.float32(self.t)
        for i in range(len(g)):
            p, self.m[i], self.v[i] = progs.adamw(
                jax.device_put(self.w[i], dm), jax.device_put(g[i], dm),
                self.m[i], self.v[i], scale, t)
            self.w[i], g[i] = jax.device_put(p, dw), None
        return loss


def tier2_mean(pods) -> None:
    """The mean over the pods of the weights and both moments, left in
    the first pod's place."""
    first = pods[0]
    for attr, dev in (("w", first.dw), ("m", first.dm), ("v", first.dm)):
        leaves = getattr(first, attr)
        for i in range(len(leaves)):
            leaves[i] = first.progs.mean(leaves[i], *(
                jax.device_put(getattr(p, attr)[i], dev) for p in pods[1:]))


def pod_devices(devices, n_pods: int):
    """(weights device, moments device) of each pod: two devices a pod
    where there are enough, else shared in turn."""
    return [(devices[(2 * c) % len(devices)],
             devices[(2 * c + 1) % len(devices)]) for c in range(n_pods)]


@lru_cache(maxsize=None)
def programs(d: Dims, hp: tuple, bits=None) -> Programs:
    """The ``Programs`` of ``d``, the hyper-parameters ``hp`` (sorted
    items) and ``bits``, compiled once a process."""
    return Programs(d, dict(hp), bits)


def train_round(seed: int, d: Dims, hp: dict, batches, devices, bits=None):
    """One tier-2 round from the weights of ``seed``: ``batches[s][c]`` is
    (tokens, labels) of pod c at step s. Returns the loss of each step and
    pod, (steps, pods) on the host; the float32 loss of the weights each
    step starts from, on that step's batch (the losses themselves where
    ``bits`` is None); and the pods' mean weights after the round (on the
    first pod's weights device; the moments are averaged too, and
    dropped)."""
    with jax.default_matmul_precision("highest"):
        hp_items = tuple(sorted(hp.items()))
        progs = programs(d, hp_items, bits)
        exact = programs(d, hp_items)
        devs = pod_devices(devices, len(batches[0]))
        pods = [Pod(progs, weights(seed, d, dw), (dw, dm))
                for dw, dm in devs]
        losses, floats = [], []
        for step in batches:
            if bits is not None:
                floats.append([exact.loss(pod.weights(), *(
                    jax.device_put(x, pod.dw) for x in b))
                    for pod, b in zip(pods, step)])
            losses.append([pod.step(*b) for pod, b in zip(pods, step)])
        losses = np.asarray(jax.device_get(losses), np.float32)
        floats = np.asarray(jax.device_get(floats), np.float32) \
            if bits is not None else losses
        tier2_mean(pods)
        return losses, floats, pods[0].weights()
