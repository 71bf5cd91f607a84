"""Driver of the LM cell: AutoFLSat's two-tier sync as pod-level training,
through ``launch/train.py``'s own loop.

One round is one tier-2 period: ``h_sync`` local steps in each pod (one
batch of ``local_batch`` x ``seq_len`` tokens a pod a step), then one
cross-pod sync of the parameters and both AdamW moments. Set-up draws
each pod's token stream from the seed, builds the programs with
``hfl_programs`` / ``hfl_sync`` on ``hfl_mesh``, and runs one warm-up
round on a state from another seed. The window calls ``run_hfl_rounds``
one round at a time on one state made from the seed, and closes at the
end of the first round that ends after the window's length.

The weights are the benchmark's own (``bench.reference.mamba2``), drawn
from the seed in one jitted call and placed in the program's state
shardings; the reference rebuilds them from the same seed. ``check()``
replays the window's first round from the seed through the same
function, and holds the replay against the window and the reference.
"""
from __future__ import annotations

import dataclasses
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts
from bench.drivers.flsim import seed_words
from bench.reference import mamba2 as ref
# The trainer's loop: a checkout that lacks it fails here, before set-up.
from repro.launch.train import (hfl_mesh, hfl_programs, hfl_sync,
                                run_hfl_rounds)

#: Where each of the reference's layer weights sits in the program's
#: layer (``models/model.py``: one stacked superblock of ``norm1`` and
#: ``ssm``).
PROGRAM_LEAF = {"norm": ("norm1", "scale"), "in_proj": ("ssm", "in_proj"),
                "conv_w": ("ssm", "conv_w"), "conv_b": ("ssm", "conv_b"),
                "A_log": ("ssm", "A_log"), "D": ("ssm", "D"),
                "dt_bias": ("ssm", "dt_bias"),
                "gate_norm": ("ssm", "norm_scale"),
                "out_proj": ("ssm", "out_proj")}


def program_params(key, d: ref.Dims) -> dict:
    """The reference's weights of ``key`` in the program's layout."""
    layers = jax.vmap(lambda i: ref.layer_weights(ref.layer_key(key, i), d))(
        jnp.arange(d.n_layers))
    block = {"norm1": {}, "ssm": {}}
    for name, (group, leaf) in PROGRAM_LEAF.items():
        block[group][leaf] = layers[name]
    return {"tok_embed": ref.embed_weights(key, d), "layers": (block,),
            "final_norm": {"scale": jnp.ones((d.d_model,))}}


def reference_layout(params: dict, d: ref.Dims) -> dict:
    """The program's parameters of one pod, in the reference's layout."""
    block = params["layers"][0]
    return {"embed": params["tok_embed"],
            "layers": [{name: block[g][leaf][i]
                        for name, (g, leaf) in PROGRAM_LEAF.items()}
                       for i in range(d.n_layers)],
            "norm": params["final_norm"]["scale"]}


def zipf_streams(seed: int, vocab: int, pods: int, n: int, s: float):
    """``n`` token ids per pod over the whole vocabulary: a Zipf unigram
    of exponent ``s`` over ranks, each pod ranking the ids by its own
    permutation, so the pods' data differ (non-IID)."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -s
    p /= p.sum()
    return np.stack([rng.permutation(vocab)[rng.choice(vocab, n, p=p)]
                     for _ in range(pods)]).astype(np.int32)


class Sim:
    """The LM cell: ``config`` and ``traffic`` are the parsed files,
    ``seed`` the run's seed, ``log`` prints to standard error."""

    def __init__(self, config: dict, traffic: dict, seed: int, log):
        self.config, self.traffic, self.seed, self.log = config, traffic, \
            seed, log
        self.dims = ref.Dims.of(config["model"])
        self.pods = int(config["deployment"]["pods"])
        self.h_sync = int(traffic["h_sync"])
        self.local_batch = int(traffic["local_batch"])
        self.seq = int(traffic["seq_len"])
        self.hp = traffic["adamw"]
        if traffic["quant_bits"]:
            raise ValueError("the reference has no quantized sync")
        (self.data_seed, self.warm_seed, self.weight_seed, self.replay_seed
         ) = seed_words(seed, 0, n=4)
        self.round_seeds, self.losses = [], []
        self.window_rounds = 0

    # -- the program ---------------------------------------------------
    def program_cfg(self):
        """The registered config at the configuration file's widths and
        compute dtype (at published widths only the dtype is set)."""
        from repro.configs import get_config
        m = self.config["model"]
        base = get_config(self.config["arch"])
        ssm = dataclasses.replace(
            base.ssm, **{k: m[k] for k in ("d_state", "head_dim", "expand",
                                           "n_groups", "conv_width", "chunk",
                                           "dt_min", "dt_max")})
        return dataclasses.replace(
            base, ssm=ssm, compute_dtype=m["compute_dtype"],
            **{k: m[k] for k in ("n_layers", "d_model", "vocab", "norm_eps")})

    def draw_tokens(self):
        """The pods' token streams from the seed: (pods, pool rounds,
        h_sync, local_batch, seq_len + 1)."""
        pool = int(self.traffic["pool_rounds"])
        per_pod = pool * self.h_sync * self.local_batch * (self.seq + 1)
        return zipf_streams(
            self.data_seed, self.dims.vocab, self.pods, per_pod,
            float(self.traffic["zipf_s"])).reshape(
                self.pods, pool, self.h_sync, self.local_batch, self.seq + 1)

    def setup(self) -> None:
        from repro.core.hierarchy import hfl_state_specs
        from repro.optim.optimizers import AdamWConfig
        from repro.sharding.partition import named
        from repro.train.steps import TrainState
        self.tokens = self.draw_tokens()
        cfg = self.program_cfg()
        mesh = hfl_mesh(self.pods)
        self.chips = mesh.devices.size
        opt = AdamWConfig(**self.hp)
        _, self.local, self.place_batch = hfl_programs(cfg, opt, mesh,
                                                       self.pods)
        self.sync = hfl_sync(cfg, mesh, int(self.traffic["quant_bits"]))
        d, pods = self.dims, self.pods

        def state(seed):
            params = program_params(jax.random.PRNGKey(seed), d)
            zeros = jax.tree.map(jnp.zeros_like, params)
            bcast = lambda t: jax.tree.map(
                lambda x: jnp.broadcast_to(x, (pods,) + x.shape), t)
            return TrainState(params=bcast(params), opt={
                "m": bcast(zeros), "v": bcast(zeros),
                "step": jnp.zeros((pods,), jnp.int32)})
        self.make_state = jax.jit(
            state, out_shardings=named(mesh, hfl_state_specs(cfg, mesh)))
        with jax.profiler.TraceAnnotation("bench.warmup"):
            warm, _ = self._round(self.make_state(self.warm_seed), 0,
                                  self.warm_seed)
            jax.block_until_ready(warm)
            del warm
        self.state = self.make_state(self.weight_seed)
        jax.block_until_ready(self.state)

    def batches(self, r: int):
        """The pods' batches of round ``r``: per step, one host batch a
        pod. The pool of rounds drawn in set-up is reused in turn."""
        block = self.tokens[:, r % self.tokens.shape[1]]
        for s in range(self.h_sync):
            yield [{"tokens": block[c, s, :, :-1], "labels": block[c, s, :, 1:]}
                   for c in range(self.pods)]

    def _round(self, state, r: int, seed: int):
        return run_hfl_rounds(state, self.local, self.sync, self.place_batch,
                              self.batches(r), self.h_sync, self.h_sync,
                              seed=seed)

    # -- the window ----------------------------------------------------
    def window(self, seconds: float):
        """Run rounds for ``seconds``; returns (attempted, failed, elapsed)
        in rounds and host seconds."""
        attempted = failed = 0
        t0 = time.perf_counter()
        while True:
            seed = seed_words(self.seed, 1, attempted)[0]
            attempted += 1
            try:
                self.state, losses = self._round(self.state, attempted - 1,
                                                 seed)
            except Exception:
                self.log(traceback.format_exc())
                failed += 1
                break
            self.round_seeds.append(seed)
            self.losses.append(losses)
            failed += int(not np.all(np.isfinite(losses)))
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(self.state)
        self.window_rounds = len(self.losses)
        return attempted, failed, time.perf_counter() - t0

    def end_to_end(self, elapsed: float) -> dict:
        """The window's end-to-end metrics, by name, besides ``setup_s``."""
        return {"rounds_per_s": self.window_rounds / elapsed}

    # -- correctness ---------------------------------------------------
    def replay(self):
        """The window's first round again, from the seed: (losses, the
        reference's loss of the weights each step starts from, the first
        pod's parameters on the host, the largest difference between the
        pods' parameters and moments after the sync). The step program is
        the window's; before each step the reference reads the weights
        that step starts from, on the benchmark's own batches."""
        self.state = None
        d = self.dims

        def pod_loss(params, tokens, labels):
            block = params["layers"][0]
            return ref.stacked_loss(
                params["tok_embed"],
                {name: block[g][leaf] for name, (g, leaf)
                 in PROGRAM_LEAF.items()},
                params["final_norm"]["scale"], tokens, labels, d)
        ref_loss = jax.jit(jax.vmap(pod_loss))
        steps = iter(self.batches(0))
        at_weights = []

        def local(state, batch):
            host = next(steps)
            with jax.default_matmul_precision("highest"):
                at_weights.append(jax.device_get(ref_loss(
                    state.params, *(np.stack([b[k] for b in host])
                                    for k in ("tokens", "labels")))))
            return self.local(state, batch)
        state, losses = run_hfl_rounds(
            self.make_state(self.weight_seed), local, self.sync,
            self.place_batch, self.batches(0), self.h_sync, self.h_sync,
            seed=self.replay_seed)
        spread = jax.jit(lambda x: jnp.max(jnp.abs(x[1:] - x[:1])))
        gap = float(max(jax.device_get([spread(x) for x in jax.tree.leaves(
            (state.params, state.opt["m"], state.opt["v"]))])))
        params = jax.device_get(state.params)
        del state
        first = jax.tree.map(lambda x: x[0], params)
        return (losses, np.asarray(at_weights, np.float32),
                reference_layout(first, d), gap)

    def reference_batches(self, r: int):
        return [[(b["tokens"], b["labels"]) for b in step]
                for step in self.batches(r)]

    def check(self) -> dict:
        """The compared numbers: the replay against the window's first
        round, then the replay against the reference."""
        if not self.losses:
            return {"replay_loss_diff": float("inf"),
                    "loss_gap": float("inf"), "update_gap": float("inf"),
                    "replica_gap": float("inf")}
        t0 = time.perf_counter()
        losses, at_weights, params, gap = self.replay()
        numbers = {"replay_loss_diff": float(np.max(np.abs(
            losses - self.losses[0]))), "replica_gap": gap}
        t1 = time.perf_counter()
        ref_losses, _, w = ref.train_round(
            self.weight_seed, self.dims, self.hp, self.reference_batches(0),
            jax.devices())
        t2 = time.perf_counter()
        numbers.update(compare(losses, at_weights, params, w,
                               self.weight_seed, self.dims))
        per_step = lambda other: np.max(
            np.abs(losses - other) / np.abs(other), axis=1).tolist()
        self.log(f"check: replay {t1 - t0:.1f} s, reference "
                 f"{t2 - t1:.1f} s, comparison "
                 f"{time.perf_counter() - t2:.1f} s; per step, the loss gap "
                 f"at the same weights {per_step(at_weights)}, to the "
                 f"reference's own round {per_step(ref_losses)}")
        return numbers

    # -- what the per-layer readers need --------------------------------
    def window_tokens(self) -> int:
        return self.window_rounds * self.h_sync * self.pods \
            * self.local_batch * self.seq

    def train_flops_per_token(self) -> float:
        d, m = self.dims, self.config["model"]
        return counts.mamba2_train_flops_per_token(
            d.d_model, d.n_layers, d.vocab, d.d_state, d.head_dim, d.expand,
            d.n_groups, d.conv_width, min(m["chunk"], self.seq))


def compare(losses, at_weights, params, ref_w, seed: int, d: ref.Dims
            ) -> dict:
    """``loss_gap``: the largest relative gap over the round's step losses
    of each pod, each against the reference's loss of the weights that
    step started from (``at_weights``, same shape). ``update_gap``: by the
    worst leaf, the norm of the difference between the program's and the
    reference's change of the weights over the round, against the larger
    of that leaf's reference change and the median leaf's. ``params`` is
    the program's first pod after the sync on the host, ``ref_w`` the
    reference's mean; both in the reference's layout."""
    loss_gap = float(np.max(np.abs(losses - at_weights)
                            / np.abs(at_weights)))
    dev = jax.tree.leaves(ref_w)[0].devices().pop()
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    init = ref.weights(seed, d, dev)
    pairs = jax.tree.map(
        lambda p, r, i: (norm(jax.device_put(p, dev), r), norm(r, i)),
        params, ref_w, init)
    diff, change = (np.asarray(jax.device_get(
        [x[k] for x in jax.tree.leaves(
            pairs, is_leaf=lambda x: isinstance(x, tuple))]))
        for k in range(2))
    base = np.maximum(change, np.median(change))
    return {"loss_gap": loss_gap,
            "update_gap": float(np.max(diff / base))}
