"""FedBuff trains its buffer in one program at the flush.

A return queues a pending entry and launches no device work; the flush
trains every entry in one ``local_sgd_buffer`` dispatch and folds the
stacked result. Checked here: the buffer program's rows equal the
per-return ``local_sgd`` rows one by one, with the same key chain; the
flush equals the per-return path's fold, with the QuAFL round trip and
poisoned payloads applied per row; and whole runs make no single-client
call, compile the buffer program once, count every trained entry, and
stay bitwise equal to the retained loop."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.aggregation import apply_buffered_deltas
from repro.core.client import (clear_train_caches, local_sgd,
                               local_sgd_buffer, train_cache_sizes)
from repro.core.contact_plan import build_contact_plan
from repro.core.quantize import quantize_roundtrip
from repro.core.round_loop_ref import run_fedbuff_loop
from repro.core.spaceify import FedBuffSat, FLConfig
from repro.data.synthetic import make_federated_dataset
from repro.models.small import MODELS
from repro.sim.faults import FaultConfig, PoisonAttack
from repro.sim.hardware import HardwareProfile

HW = HardwareProfile(name="fast", epoch_time_s=50.0, downlink_rate_bps=8e9,
                     uplink_rate_bps=8e9, isl_rate_bps=8e9)
LR, MU = 0.05, 0.01


@pytest.fixture(scope="module")
def plan():
    return build_contact_plan(2, 3, 2, horizon_s=0.8 * 86400, dt_s=60.0)


@pytest.fixture(scope="module")
def ds():
    return make_federated_dataset("femnist", 6, 32)


def _versions(model, ds):
    """Two global versions a buffer's returns may have picked up."""
    g0 = MODELS[model][0](jax.random.PRNGKey(0), tuple(ds.x.shape[2:]),
                          ds.n_classes)
    return g0, jax.tree.map(lambda p: 1.01 * p + 1e-3, g0)


def _per_return(model, ds, bases, sats, epochs, key, batch_size):
    """What the returns did before: a key split and one ``local_sgd`` call
    each, FedProx anchored at the return's own base."""
    rows = []
    for base, k, e in zip(bases, sats, epochs):
        key, sub = jax.random.split(key)
        rows.append(local_sgd(model, base, ds.x[int(k)], ds.y[int(k)], sub,
                              int(e), batch_size, LR, MU, True, base))
    return rows, key


def _assert_bitwise(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("model,dataset", [("mlp", "femnist"),
                                           ("cnn", "eurosat")])
def test_buffer_rows_equal_per_return_local_sgd(model, dataset):
    """Different base versions, epoch budgets of 1 and 3, FedProx on: each
    row of the buffer program equals that return's ``local_sgd``, bit for
    bit on the CPU, and the key comes back D sequential splits on."""
    ds = make_federated_dataset(dataset, 6, 32)
    g0, g1 = _versions(model, ds)
    bases = (g0, g1, g1, g0, g1)
    sats = np.array([3, 1, 3, 5, 0], np.int32)
    epochs = np.array([1, 3, 1, 3, 3], np.int32)
    key = jax.random.PRNGKey(7)
    trained, key_out = local_sgd_buffer(model, bases, ds.x, ds.y, sats, key,
                                        epochs, 16, LR, MU)
    rows, key_want = _per_return(model, ds, bases, sats, epochs, key, 16)
    for i, row in enumerate(rows):
        _assert_bitwise(jax.tree.map(lambda p: p[i], trained), row)
    np.testing.assert_array_equal(np.asarray(key_out), np.asarray(key_want))


@pytest.mark.parametrize("quant_bits,poison", [(0, False), (8, False),
                                               (0, True), (8, True)])
def test_flush_equals_the_per_return_fold(plan, ds, quant_bits, poison):
    """One flush gives, bit for bit, what the per-return path gave: each
    return trained alone, its model round-tripped through the wire format
    and poisoned where its satellite is compromised, then the rows folded
    by ``apply_buffered_deltas``."""
    faults = FaultConfig(poison=PoisonAttack(satellites=(1, 5), scale=5.0),
                         seed=3) if poison else None
    algo = FedBuffSat(plan, HW, ds, FLConfig(
        model="mlp", batch_size=16, quant_bits=quant_bits, faults=faults))
    g0, g1 = _versions("mlp", ds)
    bases = (g1, g0, g1, g1, g0)
    sats = np.array([1, 2, 5, 0, 1], np.int32)
    epochs = np.array([3, 1, 1, 3, 2], np.int32)
    wgts = [1.0, 0.5 ** 0.5, 1.0, 1.0, 3.0 ** -0.5]
    start, g = algo.key, algo.global_params
    algo._flush_buffer([(int(k), b, int(e), w, 60.0 * i) for i, (k, b, e, w)
                        in enumerate(zip(sats, bases, epochs, wgts))])

    rows, key_want = _per_return("mlp", ds, bases, sats, epochs, start, 16)
    if quant_bits:
        rows = [quantize_roundtrip(r, quant_bits) for r in rows]
    if poison:
        rows = [jax.tree.map(lambda p, b: (6.0 * b - 5.0 * p), r, b)
                if k in (1, 5) else r for r, b, k in zip(rows, bases, sats)]
    want = apply_buffered_deltas(g, list(zip(rows, bases)),
                                 np.asarray(wgts, np.float32))
    _assert_bitwise(algo.global_params, want)
    np.testing.assert_array_equal(np.asarray(algo.key), np.asarray(key_want))
    assert algo._last_flush_corrupted == (3 if poison else 0)


RUNS = {
    "plain": {},
    "quant8": {"quant_bits": 8},
    "payload_faults": {"faults": FaultConfig(
        corrupt_prob=0.3, poison=PoisonAttack(satellites=(2,), scale=5.0),
        seed=3)},
    "robust": {"aggregator": "median"},
}


@pytest.mark.parametrize("case", sorted(RUNS))
def test_run_trains_each_buffer_in_one_program(plan, ds, case):
    """A multi-round run makes no single-client ``local_sgd`` call, holds
    one entry in the buffer program's cache, counts ``buffer_size`` trained
    entries a round, and stays bitwise equal to the retained loop."""
    cfg = FLConfig(model="mlp", batch_size=16, max_rounds=4,
                   max_local_epochs=6, buffer_size=3, **RUNS[case])
    clear_train_caches()
    algo = FedBuffSat(plan, HW, ds, cfg)
    recs = algo.run()
    retained = FedBuffSat(plan, HW, ds, dataclasses.replace(cfg))
    recs_ref = run_fedbuff_loop(retained)
    assert len(recs) == cfg.max_rounds
    assert train_cache_sizes()["local_sgd"] == 0
    assert train_cache_sizes()["local_sgd_buffer"] == 1
    assert algo.trace.counters["fedbuff_buffered_trains"] \
        == cfg.buffer_size * len(recs)
    assert algo.trace.spans["fl.train"][0] == len(recs)
    assert [dataclasses.astuple(r) for r in recs] \
        == [dataclasses.astuple(r) for r in recs_ref]
    _assert_bitwise(algo.global_params, retained.global_params)
    if case == "payload_faults":
        assert sum(r.corrupted_updates for r in recs) > 0
    if case == "robust":
        assert sum(r.clipped_updates for r in recs) > 0
