"""Model aggregation: in-place (fixed-memory) weighted accumulation and
the pluggable Byzantine-robust aggregation layer.

The paper's FLyCubes use Flower's in-place aggregation to stay inside 512 MB
(Fig. 7). ``inplace_aggregate`` reproduces those semantics: a running
accumulator the size of ONE model, fed a stream of (params, weight); the
Pallas kernel ``repro.kernels.quant_agg`` fuses the dequantize+accumulate
step for quantized (QuAFL) updates on TPU.

Robust aggregation (``FLConfig.aggregator``)
--------------------------------------------
The radiation environment that resets payload computers also flips bits
*silently* (``FaultConfig.corrupt_prob``), and the IWQoS'23 adversarial
framing extends from energy-drain to poisoned updates
(``FaultConfig.poison``) — either way a single bad row reaching the
plain weighted mean can destroy the global model. The
:class:`RobustAggregator` hierarchy is the defense layer: fixed-shape,
pad-row-safe estimators over the ``(K, ...)`` stacked cohort, selected
by name via ``FLConfig.aggregator``:

  * ``norm_clip`` — each row's update (delta from the broadcast
    reference) is clipped to ``multiplier`` x the cohort's median delta
    norm before the weighted mean: bounds how far any one row can drag
    the aggregate while keeping data-size weighting.
  * ``trimmed_mean`` — coordinate-wise: sort the valid rows per
    coordinate, drop the ``trim`` fraction from each end, average the
    rest (rank-based, unweighted — Byzantine estimators order rows, they
    don't trust client-reported sample counts).
  * ``median`` — coordinate-wise median (the maximally trimmed mean).
  * ``krum`` — Krum distance score (Blanchard et al.): each row is
    scored by the summed squared distance to its m-f-2 nearest cohort
    peers; the best-scoring single row becomes the aggregate.

All of them are batched jnp/Pallas ops over the fixed cohort width —
pad slots (weight 0) are pushed to +inf so they sort last under exact-0
rank weight, and the rank-based pair (trimmed mean / median) routes
through the fused ``trimmed_agg_stacked`` Pallas kernel
(``repro.kernels.trimmed_agg``: compiled on TPU, jnp sort fallback on
CPU, interpret in tests — the same routing contract as ``quant_agg``).
``aggregator=None`` keeps the exact pre-existing weighted-mean path, so
the default engine stays bitwise-identical.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp

from repro import obs


@jax.jit
def _weighted_average_impl(stacked_params, w):
    def avg(leaf):
        wb = w.reshape((-1,) + (1,) * (leaf.ndim - 1))
        # zero-weight rows (padded cohort slots) are forced to exact +0.0
        # rather than relying on 0*x: a non-finite pad row (0*inf = NaN)
        # must not poison the aggregate of the real cohort members.
        terms = jnp.where(wb > 0, leaf.astype(jnp.float32) * wb, 0.0)
        # strictly-ordered accumulation loop, NOT a reduction tree (the
        # loop-carried dependence pins the float-add order): appending
        # zero-weight rows — the padded round engine's masked cohort
        # slots — is an exact IEEE no-op, so the result is bitwise
        # independent of the padding width.
        acc = jax.lax.fori_loop(
            0, leaf.shape[0], lambda i, a: a + terms[i],
            jnp.zeros(leaf.shape[1:], jnp.float32))
        return acc.astype(leaf.dtype)

    return jax.tree.map(avg, stacked_params)


def weighted_average(stacked_params, weights):
    """stacked_params: pytree with leading client axis (K, ...); weights (K,)."""
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.maximum(w.sum(), 1e-9)
    return _weighted_average_impl(stacked_params, w)


def inplace_aggregate(updates: Iterable[Tuple], template=None):
    """Accumulate a stream of (params, weight) in fixed memory.

    Returns the weighted average without ever materializing more than one
    accumulator + one incoming model (Flower in-place semantics).
    """
    acc = None
    total = 0.0
    for params, w in updates:
        w = float(w)
        if acc is None:
            acc = jax.tree.map(lambda p: p.astype(jnp.float32) * w, params)
        else:
            acc = jax.tree.map(lambda a, p: a + p.astype(jnp.float32) * w,
                               acc, params)
        total += w
    if acc is None:
        raise ValueError("no updates")
    return jax.tree.map(lambda a: a / total, acc)


@partial(jax.jit, static_argnames=("bits", "mode"))
def quantized_weighted_average(stacked_params, weights, bits: int,
                               mode: str = "auto"):
    """Weighted average over the QuAFL wire format: each client row of the
    stacked pytree is quantized to ``bits`` with its own per-tensor scale,
    then the server dequantizes + accumulates the whole cohort through the
    fused ``quant_agg`` kernel (``mode``: "auto" | "pallas" |
    "pallas_interpret" | "jnp" — see repro.kernels.ops). One program per
    call: the weights' normalisation, every leaf's quantization and the
    kernel's tiling are traced in with the kernel.

    Zero-weight rows (padded cohort slots) contribute nothing: their
    weight*scale product is 0."""
    from repro.core.quantize import quantize_stacked
    from repro.kernels.ops import quantized_stacked_accumulate

    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.maximum(w.sum(), 1e-9)

    def agg(leaf):
        q, scale = quantize_stacked(leaf, bits)
        acc = jnp.zeros(leaf.shape[1:], jnp.float32)
        # zero-weight rows contribute exactly 0 even if their scale is
        # non-finite (a NaN pad row would otherwise give sw = 0*NaN = NaN)
        sw = jnp.where(w > 0, w * scale, 0.0)
        out = quantized_stacked_accumulate(acc, q, sw, mode=mode)
        return out.astype(leaf.dtype)

    return jax.tree.map(agg, stacked_params)


@jax.jit
def stack_rows(rows):
    """A sequence of pytrees of one structure -> one pytree whose leaves
    carry a leading row axis (D, ...), in one program."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *rows)


def _add_mean_delta(global_params, stacked_new, stacked_base, weights):
    """global += mean_k of weights[k] * (new_k - base_k), over stacked
    (D, ...) new and base models."""
    def upd(g, n, b):
        wb = weights.reshape((-1,) + (1,) * (n.ndim - 1))
        d = (wb * (n.astype(jnp.float32) - b.astype(jnp.float32))).mean(0)
        return (g.astype(jnp.float32) + d).astype(g.dtype)
    return jax.tree.map(upd, global_params, stacked_new, stacked_base)


@jax.jit
def apply_buffered_deltas(global_params, rows, weights):
    """Buffered stale deltas as one program: global += mean_k of
    weights[k] * (new_k - base_k). ``rows`` is a sequence of
    ``(new_k, base_k)`` model pairs, stacked inside; one trace per
    length. The straggler fold's form."""
    return _add_mean_delta(global_params, *stack_rows(rows), weights)


@jax.jit
def apply_stacked_deltas(global_params, stacked_new, bases, weights):
    """``apply_buffered_deltas`` with the new models already stacked
    (D, ...), as the buffer's training program returns them, and the
    ``bases`` a sequence of D models, stacked inside. The FedBuff flush's
    form: the same math in one program, with no per-row unstacking."""
    return _add_mean_delta(global_params, stacked_new, stack_rows(bases),
                           weights)


@partial(jax.jit, static_argnames=("n_segments",))
def segment_mean(stacked_params, n_segments: int):
    """Mean over contiguous equal-size segments of the leading axis:
    (S*m, ...) -> (S, ...). The tier-1 AutoFLSat cluster aggregation for
    all clusters in one dispatch."""
    def f(leaf):
        seg = leaf.reshape((n_segments, -1) + leaf.shape[1:])
        return seg.astype(jnp.float32).mean(1).astype(leaf.dtype)
    return jax.tree.map(f, stacked_params)


@partial(jax.jit, static_argnames=("n_segments",))
def segment_weighted_mean(stacked_params, weights, n_segments: int):
    """``segment_mean`` with per-row weights (K,): zero-weight rows — e.g.
    satellites masked out by the battery floor — are excluded from their
    segment's mean. A segment whose weights are all zero yields zeros;
    callers must give such segments zero weight downstream."""
    def f(leaf):
        seg = leaf.reshape((n_segments, -1) + leaf.shape[1:])
        w = weights.reshape((n_segments, -1) + (1,) * (leaf.ndim - 1))
        num = jnp.where(w > 0, seg.astype(jnp.float32) * w, 0.0).sum(1)
        den = jnp.maximum(w.sum(1), 1e-9)
        return (num / den).astype(leaf.dtype)
    return jax.tree.map(f, stacked_params)


# ---------------------------------------------------------------------------
# Byzantine-robust aggregation layer
# ---------------------------------------------------------------------------


def _row_delta_norms(stacked_params, reference):
    """L2 norm of each client row's delta from ``reference``, over every
    leaf: (K,) f32. Non-finite pad rows yield non-finite norms; callers
    mask by validity before using them."""
    leaves = jax.tree_util.tree_leaves(stacked_params)
    refs = jax.tree_util.tree_leaves(reference)
    k = leaves[0].shape[0]
    sq = jnp.zeros((k,), jnp.float32)
    for leaf, r in zip(leaves, refs):
        d = leaf.astype(jnp.float32).reshape(k, -1) \
            - r.astype(jnp.float32).reshape(1, -1)
        sq = sq + (d * d).sum(1)
    return jnp.sqrt(sq)


def _flatten_rows(stacked_params):
    """Concat-ravel every leaf into one (K, N) f32 matrix of client rows."""
    leaves = jax.tree_util.tree_leaves(stacked_params)
    k = leaves[0].shape[0]
    return jnp.concatenate(
        [leaf.astype(jnp.float32).reshape(k, -1) for leaf in leaves], axis=1)


class RobustAggregator:
    """Interface for Byzantine-robust cohort aggregation.

    ``aggregate(stacked_params, weights, reference, mode)`` reduces a
    stacked cohort pytree (leading client axis K, zero-weight rows =
    padded slots) to a single model pytree and reports how many rows the
    estimator attenuated/rejected. ``reference`` is the broadcast global
    model the cohort trained from (delta-based defenses need it);
    ``mode`` is the kernel route ("auto" | "pallas" |
    "pallas_interpret" | "jnp") for implementations with a Pallas hot
    path. Implementations must be pad-row-safe: a zero-weight row — even
    a non-finite one — must never influence the output."""

    name = "base"

    def aggregate(self, stacked_params, weights, reference, mode="auto"):
        raise NotImplementedError

    def __call__(self, stacked_params, weights, reference, mode="auto"):
        return self.aggregate(stacked_params, weights, reference, mode)


@dataclasses.dataclass(frozen=True)
class NormClipAggregator(RobustAggregator):
    """Clip each row's update norm to ``multiplier`` x the cohort median
    delta norm, then take the usual data-weighted mean. The mildest
    defense: honest heavy-hitters are merely shrunk, a poisoned
    ``scale * delta`` row loses its amplification."""

    multiplier: float = 2.0
    name = "norm_clip"

    def aggregate(self, stacked_params, weights, reference, mode="auto"):
        w = jnp.asarray(weights, jnp.float32)
        valid = w > 0
        m = int(obs.sync(valid.sum()))
        norms = _row_delta_norms(stacked_params, reference)
        srt = jnp.sort(jnp.where(valid, norms, jnp.inf))
        med = 0.5 * (srt[(m - 1) // 2] + srt[m // 2])
        limit = self.multiplier * med
        factor = jnp.where(
            valid, jnp.minimum(1.0, limit / jnp.maximum(norms, 1e-12)), 0.0)
        n_att = int(obs.sync(jnp.sum(valid & (norms > limit))))

        def clipped(leaf, r):
            fb = factor.reshape((-1,) + (1,) * (leaf.ndim - 1))
            rf = r.astype(jnp.float32)[None]
            # select, don't rely on 0 * x: a non-finite pad row must not
            # leak NaN into its (excluded, but materialized) clipped row
            row = jnp.where(fb > 0, rf + fb * (leaf.astype(jnp.float32) - rf),
                            0.0)
            return row.astype(leaf.dtype)

        rows = jax.tree.map(clipped, stacked_params, reference)
        return weighted_average(rows, w), n_att


def _rank_combine(stacked_params, valid, rank_weights, mode):
    """Apply ``trimmed_stacked_combine`` per leaf with invalid rows pushed
    to +inf (so they sort last under exact-0 rank weight)."""
    from repro.kernels.ops import trimmed_stacked_combine

    rw = jnp.asarray(rank_weights, jnp.float32)

    def f(leaf):
        vb = valid.reshape((-1,) + (1,) * (leaf.ndim - 1))
        x = jnp.where(vb, leaf.astype(jnp.float32), jnp.inf)
        return trimmed_stacked_combine(x, rw, mode=mode).astype(leaf.dtype)

    return jax.tree.map(f, stacked_params)


@dataclasses.dataclass(frozen=True)
class TrimmedMeanAggregator(RobustAggregator):
    """Coordinate-wise trimmed mean: per coordinate, sort the m valid
    rows, drop ``floor(trim * m)`` from each end, average the rest.
    Rank-based and unweighted — a Byzantine estimator orders rows rather
    than trusting client-reported sample counts. Robust to up to a
    ``trim`` fraction of corrupted rows per coordinate."""

    trim: float = 0.2
    name = "trimmed_mean"

    def aggregate(self, stacked_params, weights, reference, mode="auto"):
        w = jnp.asarray(weights, jnp.float32)
        valid = w > 0
        k = int(valid.shape[0])
        m = int(obs.sync(valid.sum()))
        lo = min(int(self.trim * m), max((m - 1) // 2, 0))
        kept = m - 2 * lo
        rw = jnp.zeros((k,), jnp.float32).at[lo:m - lo].set(1.0 / kept)
        return _rank_combine(stacked_params, valid, rw, mode), 2 * lo


@dataclasses.dataclass(frozen=True)
class MedianAggregator(RobustAggregator):
    """Coordinate-wise median (the maximally trimmed mean): breakdown
    point 1/2, the strongest rank defense — and the highest-variance
    estimate when everyone is honest."""

    name = "median"

    def aggregate(self, stacked_params, weights, reference, mode="auto"):
        w = jnp.asarray(weights, jnp.float32)
        valid = w > 0
        k = int(valid.shape[0])
        m = int(obs.sync(valid.sum()))
        mid_lo, mid_hi = (m - 1) // 2, m // 2
        rw = jnp.zeros((k,), jnp.float32)
        rw = rw.at[mid_lo].add(0.5).at[mid_hi].add(0.5)
        return _rank_combine(stacked_params, valid, rw, mode), max(m - 2, 0)


@dataclasses.dataclass(frozen=True)
class KrumAggregator(RobustAggregator):
    """Krum (Blanchard et al., NeurIPS'17): score each row by the summed
    squared distance to its m - f - 2 nearest cohort peers and adopt the
    single best-scoring row. Tolerates up to ``byzantine_f`` colluding
    rows but discards all cross-client averaging."""

    byzantine_f: int = 1
    name = "krum"

    def aggregate(self, stacked_params, weights, reference, mode="auto"):
        w = jnp.asarray(weights, jnp.float32)
        valid = w > 0
        m = int(obs.sync(valid.sum()))
        rows = jnp.where(valid[:, None], _flatten_rows(stacked_params), 0.0)
        sq = (rows * rows).sum(1)
        d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * rows @ rows.T, 0.0)
        pair_ok = valid[:, None] & valid[None, :] \
            & ~jnp.eye(d2.shape[0], dtype=bool)
        d2 = jnp.where(pair_ok, d2, jnp.inf)
        n_nb = max(min(m - self.byzantine_f - 2, m - 1), min(1, m - 1))
        srt = jnp.sort(d2, axis=1)
        score = srt[:, :n_nb].sum(1) if n_nb > 0 \
            else jnp.zeros((d2.shape[0],), jnp.float32)
        winner = int(obs.sync(jnp.argmin(jnp.where(valid, score,
                                                    jnp.inf))))
        out = jax.tree.map(lambda leaf: leaf[winner], stacked_params)
        return out, max(m - 1, 0)


ROBUST_AGGREGATORS = {
    "norm_clip": NormClipAggregator,
    "trimmed_mean": TrimmedMeanAggregator,
    "median": MedianAggregator,
    "krum": KrumAggregator,
}


def make_robust_aggregator(spec):
    """Resolve ``FLConfig.aggregator``: None / "mean" -> None (the exact
    legacy weighted-mean path), a registry name -> default-configured
    instance, an instance -> itself."""
    if spec is None or spec == "mean":
        return None
    if isinstance(spec, str):
        try:
            return ROBUST_AGGREGATORS[spec]()
        except KeyError:
            raise ValueError(
                f"unknown aggregator {spec!r}; expected one of "
                f"{sorted(ROBUST_AGGREGATORS)} or a RobustAggregator "
                "instance") from None
    if isinstance(spec, RobustAggregator):
        return spec
    raise TypeError(f"aggregator must be None, str or RobustAggregator, "
                    f"got {type(spec).__name__}")


def robust_apply_buffered_deltas(global_params, stacked_new, stacked_base,
                                 weights, aggregator, mode="auto"):
    """FedBuff flush through a robust estimator: the buffered rows become
    weighted deltas ``weights[k] * (new_k - base_k)`` and the estimator
    aggregates them against a zero reference (so norm clipping bounds
    delta norms and rank defenses act coordinate-wise on the deltas);
    global += robust_combine(deltas). Returns (params, n_attenuated)."""
    w = jnp.asarray(weights, jnp.float32)

    def delta(n, b):
        wb = w.reshape((-1,) + (1,) * (n.ndim - 1))
        return wb * (n.astype(jnp.float32) - b.astype(jnp.float32))

    deltas = jax.tree.map(delta, stacked_new, stacked_base)
    zeros = jax.tree.map(
        lambda g: jnp.zeros(g.shape, jnp.float32), global_params)
    ones = jnp.ones((w.shape[0],), jnp.float32)
    upd, n_att = aggregator.aggregate(deltas, ones, zeros, mode=mode)
    out = jax.tree.map(
        lambda g, d: (g.astype(jnp.float32) + d.astype(jnp.float32))
        .astype(g.dtype), global_params, upd)
    return out, n_att


def pytree_bytes(params, bits=32):
    return sum(p.size for p in jax.tree_util.tree_leaves(params)) * bits / 8
