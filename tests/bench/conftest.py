"""Fixtures of the benchmark's own tests: a cell's files shrunk to a size
the CPU runs in seconds (two planes of five satellites, 16x16 images, a
one-day horizon). Only sizes change; the program and its code paths are
the cell's."""
import json
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]


def shrink(spec: dict) -> dict:
    cfg = spec["config"]
    cfg["constellation"].update(planes=2, sats_per_plane=5)
    cfg["data"].update(image_shape=[16, 16, 3], per_client=16, n_test=64)
    cfg["fl"]["batch_size"] = 8
    cfg["horizon_days"] = 1.0
    if "clients_per_round" in spec["traffic"]["fl"]:
        spec["traffic"]["fl"]["clients_per_round"] = 4
        spec["traffic"]["rounds_per_job"] = 3
    return spec


@pytest.fixture
def tiny_spec():
    from bench import harness
    return lambda cell: shrink(harness.load(ROOT, cell))


@pytest.fixture
def benchmark_json():
    return json.loads((ROOT / "BENCHMARK.json").read_text())
