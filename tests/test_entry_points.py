"""Entry-point plumbing: compile-cache placement, the chip smoke's platform
gate, the benchmark harness's exit code and the sharded HFL trainer."""
import importlib.util
import pathlib
import sys

import jax
import numpy as np
import pytest

from repro.launch import train
from repro.launch.compile_cache import CACHE_DIR, use_compile_cache
from repro.optim.optimizers import AdamWConfig

REPO = pathlib.Path(__file__).resolve().parents[1]
_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def cache_config():
    """Restore JAX's cache options after a test that sets them."""
    saved = {k: getattr(jax.config, k) for k in _CACHE_OPTIONS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_keeps_env_dir(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = use_compile_cache()
    second = use_compile_cache()
    assert first == second == str(CACHE_DIR)
    assert pathlib.Path(first).parent == REPO
    assert jax.config.jax_compilation_cache_dir == first


def test_chip_smoke_refuses_cpu():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    with pytest.raises(RuntimeError, match="platform 'cpu'"):
        chip_smoke.require_tpu()


def test_benchmark_harness_fails_on_a_failed_section(monkeypatch):
    monkeypatch.syspath_prepend(str(REPO))
    from benchmarks import run
    monkeypatch.setattr(run, "SECTIONS",
                        [("broken", "a section that cannot import",
                          "benchmarks._no_such_section")])
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", ["run", "broken"])
    with pytest.raises(SystemExit) as exit_:
        run.main()
    assert exit_.value.code not in (0, None)


def test_hfl_programs_sync_to_the_cluster_mean():
    """The sharded trainer on however many devices exist: tier-1 steps
    move the clusters apart, the sync sets both to their mean."""
    args = train.parse_args(["--arch", "mamba2-1.3b", "--reduced", "--hfl",
                             "--clusters", "2", "--steps", "2",
                             "--batch", "2", "--seq", "32"])
    cfg = train.build_cfg(args)
    mesh = train.hfl_mesh(args.clusters)
    assert mesh.axis_names == ("pod", "data", "model")
    assert mesh.devices.size == jax.device_count()
    init, local, place_batch = train.hfl_programs(cfg, AdamWConfig(), mesh,
                                                  args.clusters)
    state = init(jax.random.PRNGKey(0))
    for batches in zip(*train.cluster_streams(cfg, args)):
        state, m = local(state, place_batch(batches))
    assert np.all(np.isfinite(np.asarray(m["loss"])))
    before = np.asarray(state.params["tok_embed"])
    assert np.max(np.abs(before[0] - before[1])) > 0
    state = train.hfl_sync(cfg, mesh)(state)
    after = np.asarray(state.params["tok_embed"])
    np.testing.assert_allclose(after, np.broadcast_to(before.mean(0),
                                                      after.shape),
                               rtol=1e-6, atol=1e-6)


def test_hfl_cli_prints_the_per_step_loop_losses(monkeypatch, capsys):
    """``main()``'s ``--hfl`` loop is ``run_hfl_rounds``: the losses it
    prints are those of local steps with a sync after every
    ``--sync-every`` of them and a last short round without one."""
    argv = ["--arch", "mamba2-1.3b", "--reduced", "--hfl", "--clusters", "2",
            "--steps", "5", "--sync-every", "2", "--batch", "2", "--seq", "32",
            "--log-every", "1"]
    monkeypatch.setattr(sys, "argv", ["train"] + argv)
    monkeypatch.setattr(train, "use_compile_cache", lambda: None)
    train.main()
    printed = [line.rsplit(" (", 1)[0] for line in
               capsys.readouterr().out.splitlines() if line.startswith("step")]

    args = train.parse_args(argv)
    cfg = train.build_cfg(args)
    mesh = train.hfl_mesh(args.clusters)
    init, local, place_batch = train.hfl_programs(
        cfg, AdamWConfig(lr=args.lr, warmup_steps=args.warmup), mesh,
        args.clusters)
    sync = train.hfl_sync(cfg, mesh)
    state = init(jax.random.PRNGKey(args.seed))
    streams = train.cluster_streams(cfg, args)
    expected = []
    for i in range(args.steps):
        state, m = local(state, place_batch([next(s) for s in streams]))
        if (i + 1) % 2 == 0:
            state = sync(state)
        expected.append(f"step {i:4d} loss/cluster="
                        f"{[round(float(x), 4) for x in m['loss']]}")
    assert printed == expected
