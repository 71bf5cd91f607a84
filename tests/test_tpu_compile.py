"""Compile the main path's kernels and the cohort trainer for a TPU v5e.

Nothing runs: the TPU compiler builds each program for a described v5e:2x2
topology, which catches what interpret mode cannot (block shapes Mosaic
refuses, scoped-memory overruns, programs that do not fit the device).
The topology is described inside a fixture so that importing this file
never loads the TPU library; every program compiles in this process.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.aggregation import quantized_weighted_average
from repro.core.client import _local_sgd_batch, local_sgd_buffer
from repro.data.synthetic import DATASETS
from repro.kernels.quant_agg import TILE_LANES, TILE_SUB, quant_agg_stacked_tiles
from repro.kernels.ssd_scan import ssd_chunk_pallas
from repro.kernels.swa_attention import swa_attention
from repro.kernels.trimmed_agg import trimmed_agg_tiles
from repro.models.small import init_cnn

COHORT = 50          # the 10x10 Walker-star's clients_per_round in chip_smoke
FLEET = 100          # the 10x10 Walker-star's satellites
BUFFER = 5           # FedBuff's buffer in the benchmark's cell
N_TILES = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(fn, *args, **static):
    return fn.lower(*args, interpret=False, **static).compile().as_text()


def test_quant_agg_stacked_compiles(one_chip):
    tile = (N_TILES, TILE_SUB, TILE_LANES)
    txt = _hlo(quant_agg_stacked_tiles,
               _sds(one_chip, tile, jnp.float32),
               _sds(one_chip, (COHORT,) + tile, jnp.int32),
               _sds(one_chip, (1, COHORT), jnp.float32))
    assert "tpu_custom_call" in txt


def test_trimmed_agg_compiles(one_chip):
    txt = _hlo(trimmed_agg_tiles,
               _sds(one_chip, (COHORT, N_TILES, TILE_SUB, TILE_LANES),
                    jnp.float32),
               _sds(one_chip, (1, COHORT), jnp.float32))
    assert "tpu_custom_call" in txt


def test_swa_attention_compiles(one_chip):
    qkv = _sds(one_chip, (16, 2048, 128), jnp.bfloat16)
    txt = _hlo(swa_attention, qkv, qkv, qkv, window=1024)
    assert "tpu_custom_call" in txt


def test_ssd_chunk_compiles(one_chip):
    # mamba2-1.3b widths: 64 heads of 64, d_state 128, chunk 256
    b, nc, c, h, p, n = 1, 2, 256, 64, 64, 128
    txt = _hlo(ssd_chunk_pallas,
               _sds(one_chip, (b, nc, c, h, p), jnp.float32),
               _sds(one_chip, (b, nc, c, h), jnp.float32),
               _sds(one_chip, (h,), jnp.float32),
               _sds(one_chip, (b, nc, c, h, n), jnp.float32),
               _sds(one_chip, (b, nc, c, h, n), jnp.float32))
    assert "tpu_custom_call" in txt


def test_cohort_trainer_compiles(one_chip):
    """The vmapped local-SGD dispatch at the EuroSAT cohort shape fits one
    chip (no kernel: it is plain XLA)."""
    h, w, c, n_classes = DATASETS["eurosat"]
    n_per_client = 64
    params = jax.eval_shape(lambda k: init_cnn(k, (h, w, c), n_classes),
                            jax.random.PRNGKey(0))
    stacked = jax.tree.map(
        lambda p: _sds(one_chip, (COHORT,) + p.shape, p.dtype), params)
    compiled = _local_sgd_batch.lower(
        "cnn", stacked,
        _sds(one_chip, (COHORT, n_per_client, h, w, c), jnp.float32),
        _sds(one_chip, (COHORT, n_per_client), jnp.int32),
        _sds(one_chip, (COHORT, 2), jnp.uint32),
        _sds(one_chip, (COHORT,), jnp.int32),
        32, _sds(one_chip, (), jnp.float32), _sds(one_chip, (), jnp.float32),
        False, None).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_fedbuff_buffer_trainer_compiles(one_chip):
    """FedBuff's buffer program at the benchmark cell's shapes (5 returns,
    100 satellites of 216 EuroSAT images, batch 32) fits one chip."""
    h, w, c, n_classes = DATASETS["eurosat"]
    n_per_client = 216
    params = jax.eval_shape(lambda k: init_cnn(k, (h, w, c), n_classes),
                            jax.random.PRNGKey(0))
    base = jax.tree.map(lambda p: _sds(one_chip, p.shape, p.dtype), params)
    compiled = local_sgd_buffer.lower(
        "cnn", (base,) * BUFFER,
        _sds(one_chip, (FLEET, n_per_client, h, w, c), jnp.float32),
        _sds(one_chip, (FLEET, n_per_client), jnp.int32),
        _sds(one_chip, (BUFFER,), jnp.int32),
        _sds(one_chip, (2,), jnp.uint32),
        _sds(one_chip, (BUFFER,), jnp.int32),
        32, _sds(one_chip, (), jnp.float32),
        _sds(one_chip, (), jnp.float32)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_quantized_mean_is_one_program_holding_the_kernel(one_chip):
    """The QuAFL server mean at the EuroSAT CNN's cohort shape compiles to
    one module holding one ``quant_agg`` kernel call per leaf, under the
    kernel's own name (the name the benchmark's roofline reader matches)."""
    h, w, c, n_classes = DATASETS["eurosat"]
    params = jax.eval_shape(lambda k: init_cnn(k, (h, w, c), n_classes),
                            jax.random.PRNGKey(0))
    stacked = jax.tree.map(
        lambda p: _sds(one_chip, (COHORT,) + p.shape, p.dtype), params)
    txt = quantized_weighted_average.lower(
        stacked, _sds(one_chip, (COHORT,), jnp.float32), 8,
        mode="pallas").compile().as_text()
    calls = [ln for ln in txt.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert len(calls) == len(jax.tree_util.tree_leaves(params))
    assert all(ln.split("=")[0].strip().lstrip("%").startswith(
        "quant_agg_stacked_tiles") for ln in calls)
