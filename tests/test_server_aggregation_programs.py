"""The server aggregations on the plain path run as one compiled program a
call: the QuAFL mean (``quantized_weighted_average``) and the buffered
flush's fold (``apply_stacked_deltas``, reached through ``_flush_buffer``,
which trains the buffer in one program of its own first). Programs are
counted by ``repro.obs``'s compile counter, which counts one lowering per
program; results are compared with the eager per-leaf code the jitted
programs replaced, kept here as the oracles."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.aggregation import (TrimmedMeanAggregator,
                                    quantized_weighted_average,
                                    robust_apply_buffered_deltas)
from repro.core.client import local_sgd_buffer
from repro.core.quantize import quantize_stacked
from repro.core.spaceify import FedBuffSat, SpaceifiedFL
from repro.kernels.ops import quantized_stacked_accumulate
from repro.models.small import init_cnn

COHORT = 50          # the 10x10 Walker-star's clients a round
BUFFER = 5           # FedBuff's buffer in the benchmark's cell
N_PER_CLIENT = 32    # one batch of 32: one SGD step an epoch


@pytest.fixture(scope="module")
def cnn():
    """The EuroSAT CNN's 8-leaf parameter tree."""
    params = init_cnn(jax.random.PRNGKey(0), (64, 64, 3), 10)
    assert len(jax.tree_util.tree_leaves(params)) == 8
    return params


@pytest.fixture(scope="module")
def cohort(cnn):
    """A stacked cohort of COHORT rows: the last 10 are pad slots at
    weight 0, and the last of those is non-finite."""
    keys = jax.random.split(jax.random.PRNGKey(1), len(cnn))
    leaves, tree = jax.tree_util.tree_flatten(cnn)
    stacked = [leaf + 0.05 * jax.random.normal(k, (COHORT,) + leaf.shape)
               for leaf, k in zip(leaves, keys)]
    stacked = [s.at[-1].set(jnp.nan) for s in stacked]
    w = np.linspace(100.0, 300.0, COHORT)
    w[40:] = 0.0
    return jax.tree_util.tree_unflatten(tree, stacked), w


@pytest.fixture(scope="module")
def buffer(cnn):
    """BUFFER pending entries (satellite, base, epochs, staleness weight,
    return time) as FedBuff queues them, over BUFFER satellites' data."""
    rows = []
    for i in range(BUFFER):
        ks = jax.random.split(jax.random.PRNGKey(10 + i), len(cnn))
        leaves, tree = jax.tree_util.tree_flatten(cnn)
        base = [leaf + 0.01 * jax.random.normal(k, leaf.shape)
                for leaf, k in zip(leaves, ks)]
        rows.append((BUFFER - 1 - i, jax.tree_util.tree_unflatten(tree, base),
                     1 + i % 2, (1.0 + i) ** -0.5, 100.0 * i))
    kx, ky = jax.random.split(jax.random.PRNGKey(3))
    data = types.SimpleNamespace(
        x=jax.random.normal(kx, (BUFFER, N_PER_CLIENT, 64, 64, 3)),
        y=jax.random.randint(ky, (BUFFER, N_PER_CLIENT), 0, 10))
    return rows, data


class _Server:
    """The slice of an engine that ``_flush_buffer`` uses."""
    faults = None
    _apply_stacked_deltas = SpaceifiedFL._apply_stacked_deltas
    _robust_deltas = SpaceifiedFL._robust_deltas
    _flush_buffer = FedBuffSat._flush_buffer

    def __init__(self, global_params, data, aggregator=None):
        self.global_params, self.aggregator = global_params, aggregator
        self.ds, self.key = data, jax.random.PRNGKey(5)
        self.cfg = types.SimpleNamespace(
            model="cnn", batch_size=32, lr=0.05, prox_mu=0.01, quant_bits=0,
            quant_kernel="jnp")


@pytest.fixture(scope="module")
def trained(cnn, buffer):
    """The buffer's trained models (BUFFER, ...) and the advanced key, as
    the flush's training program gives them to the fold."""
    rows, data = buffer
    return local_sgd_buffer(
        "cnn", tuple(b[1] for b in rows), data.x, data.y,
        np.array([b[0] for b in rows], np.int32), jax.random.PRNGKey(5),
        np.array([b[2] for b in rows], np.int32), 32, 0.05, 0.01)


def _programs_lowered(fn):
    trace = obs.RunTrace(0, "test")
    with obs.recording(trace):
        jax.block_until_ready(fn())
    return trace.counters.get("compiles", 0)


def _eager_quantized_mean(stacked_params, weights, bits, mode):
    """The QuAFL mean as it ran before it was jitted: per leaf, op by op."""
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.maximum(w.sum(), 1e-9)

    def agg(leaf):
        q, scale = quantize_stacked(leaf, bits)
        acc = jnp.zeros(leaf.shape[1:], jnp.float32)
        sw = jnp.where(w > 0, w * scale, 0.0)
        out = quantized_stacked_accumulate(acc, q, sw, mode=mode)
        return out.astype(leaf.dtype)

    return jax.tree.map(agg, stacked_params)


@jax.jit
def _stacked_flush(global_params, stacked_new, stacked_base, weights):
    """The buffered flush's reduction as it ran before it took rows."""
    def upd(g, n, b):
        wb = weights.reshape((-1,) + (1,) * (n.ndim - 1))
        d = (wb * (n.astype(jnp.float32) - b.astype(jnp.float32))).mean(0)
        return (g.astype(jnp.float32) + d).astype(g.dtype)
    return jax.tree.map(upd, global_params, stacked_new, stacked_base)


def _eager_stacks(stacked_new, buf):
    stacked_base = jax.tree.map(lambda *xs: jnp.stack(xs),
                                *[b[1] for b in buf])
    return stacked_new, stacked_base, jnp.asarray([b[3] for b in buf],
                                                  jnp.float32)


@pytest.fixture(scope="module")
def first_calls(cohort):
    """Programs lowered by a first and a second call of the jitted mean
    through the interpreted kernel, with caches cleared, and its result."""
    stacked, w = cohort
    jax.clear_caches()
    out = []

    def call():
        out.append(quantized_weighted_average(stacked, w, 8,
                                              mode="pallas_interpret"))
        return out[-1]

    return _programs_lowered(call), _programs_lowered(call), out[0]


def test_quantized_mean_lowers_one_program(first_calls):
    """A first call lowers one program (the eager code lowered 112 on this
    tree); a second call with the same shapes lowers none."""
    assert first_calls[:2] == (1, 0)


def test_plain_flush_lowers_one_program(cnn, buffer):
    """A first 5-entry plain flush lowers one program to fold the buffer
    (the eager stacks lowered 18), beside the one that trains it; a second
    flush of that length lowers none."""
    jax.clear_caches()

    def lowered():
        srv = _Server(cnn, buffer[1])
        trace = obs.RunTrace(0, "test")
        with obs.recording(trace):
            srv._flush_buffer(buffer[0])
            jax.block_until_ready(srv.global_params)
        c = trace.counters
        return (c.get("compiles", 0), c.get("compiles.fl.train", 0),
                c.get("compiles.fl.aggregate", 0))

    assert lowered() == (2, 1, 1)
    assert lowered() == (0, 0, 0)


@pytest.mark.parametrize("mode", ["pallas_interpret", "jnp"])
def test_quantized_mean_matches_eager_per_leaf(cohort, first_calls, mode):
    """The jitted mean equals the eager per-leaf computation within 1e-6
    of each leaf's largest value, with a non-finite pad row at weight 0 in
    the cohort. (Fused, the quantization's scale can round one ulp apart
    from the eager op's, so an entry near 0 is compared on its leaf's
    scale.)"""
    stacked, w = cohort
    got = first_calls[2] if mode == "pallas_interpret" else \
        quantized_weighted_average(stacked, w, 8, mode=mode)
    want = _eager_quantized_mean(stacked, w, 8, mode)
    for g, e in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, e = np.asarray(g), np.asarray(e)
        assert np.isfinite(g).all()
        assert np.abs(g - e).max() <= 1e-6 * np.abs(e).max()


def test_plain_flush_equals_stack_then_flush_bitwise(cnn, buffer, trained):
    srv = _Server(cnn, buffer[1])
    srv._flush_buffer(buffer[0])
    want = _stacked_flush(cnn, *_eager_stacks(trained[0], buffer[0]))
    assert srv._last_flush_clipped == srv._last_flush_corrupted == 0
    np.testing.assert_array_equal(np.asarray(srv.key), np.asarray(trained[1]))
    for g, e in zip(jax.tree_util.tree_leaves(srv.global_params),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e))


def test_robust_flush_takes_the_jitted_stack(cnn, buffer, trained):
    """The robust flush sees the same stacked rows as eager stacks would
    give it, and reports the estimator's trimmed row count."""
    agg = TrimmedMeanAggregator(trim=0.2)
    srv = _Server(cnn, buffer[1], agg)
    srv._flush_buffer(buffer[0])
    want, n_att = robust_apply_buffered_deltas(
        cnn, *_eager_stacks(trained[0], buffer[0]), agg, mode="jnp")
    assert srv._last_flush_clipped == n_att == 2
    for g, e in zip(jax.tree_util.tree_leaves(srv.global_params),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e))
