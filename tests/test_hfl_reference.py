"""The LM cell's plain reference (``bench/reference/mamba2.py``) against
the program's two-pod round, at ``mamba2_1p3b.smoke()`` widths on the CPU,
on the benchmark's seeded weights: the program's round, replayed through
``launch/train.py``'s ``run_hfl_rounds``, is held against the reference's
with the cell's own comparison (``bench.drivers.hfl.compare``)."""
import pathlib

import pytest

from bench import compare, control_hfl, harness
from bench.drivers import hfl
from repro.configs.mamba2_1p3b import smoke

ROOT = pathlib.Path(__file__).resolve().parents[1]
CELL = "mamba2-1.3b.hfl2x2-4chip"
SEED = 2 ** 33 + 7

#: Both sides in float32: they differ only in the order of their sums (the
#: chunked SSD against the whole-sequence sum, XLA's fusions), which moves
#: the losses by about 1e-7 relative (1.5e-7 read) and each leaf's AdamW
#: update, whose normalisation magnifies gradients near zero, by about
#: 1e-5 (1.7e-5 read).
F32_TOL = {"replay_loss_diff": 0.0, "replica_gap": 0.0,
           "loss_gap": 1e-5, "update_gap": 1e-3}
#: The program in bfloat16: its matmul operands keep 8 significant bits, so
#: the losses move by about 1e-4 relative (1.3e-4 read) and the updates by
#: a few % (0.029 read); the control, whose operands keep 4, reads 1.2e-3
#: and 0.168, more than three times over each limit.
BF16_TOL = {"replay_loss_diff": 0.0, "replica_gap": 0.0,
            "loss_gap": 3e-4, "update_gap": 0.05}


def smoke_spec(dtype: str) -> dict:
    """The cell's files at the smoke config's widths, 64 tokens a step."""
    cfg = smoke()
    spec = harness.load(ROOT, CELL)
    spec["config"]["model"].update(
        n_layers=cfg.n_layers, d_model=cfg.d_model, vocab=cfg.vocab,
        d_state=cfg.ssm.d_state, head_dim=cfg.ssm.head_dim,
        chunk=cfg.ssm.chunk, compute_dtype=dtype)
    spec["traffic"].update(seq_len=64, pool_rounds=2)
    return spec


def program_numbers(spec: dict) -> dict:
    sim = hfl.Sim(spec["config"], spec["traffic"], SEED, print)
    sim.setup()
    sim.window(0.0)
    return sim.check()


def test_float32_round_matches_the_reference():
    numbers = program_numbers(smoke_spec("float32"))
    assert compare.verdict(numbers, F32_TOL), numbers


def test_bfloat16_round_matches_and_the_control_does_not():
    spec = smoke_spec("bfloat16")
    numbers = program_numbers(spec)
    assert compare.verdict(numbers, BF16_TOL), numbers
    control = control_hfl.control_numbers(smoke_spec("bfloat16"), SEED,
                                          print)
    assert not compare.verdict(control, BF16_TOL), control
    for k in ("loss_gap", "update_gap"):
        assert control[k] > 3 * numbers[k], (k, control, numbers)


def test_forward_losses_are_the_training_loss():
    """The reference's forward-only losses, which the comparison takes at
    the weights each step of the program (``stacked_loss``, layers
    stacked) and of the control (``Programs.loss``, layer by layer)
    starts from, are the loss its own training step computes there."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench.reference import mamba2 as ref
    spec = smoke_spec("float32")
    d = ref.Dims.of(spec["config"]["model"])
    hp = spec["traffic"]["adamw"]
    w = ref.weights(3, d, jax.devices()[0])
    rng = np.random.default_rng(3)
    tokens, labels = (jnp.asarray(rng.integers(0, d.vocab, (1, 64)),
                                  jnp.int32) for _ in range(2))
    with jax.default_matmul_precision("highest"):
        progs = ref.programs(d, tuple(sorted(hp.items())))
        want = float(progs.grads(w, tokens, labels)[0])
        layers = jax.tree.map(lambda *xs: jnp.stack(xs), *w["layers"])
        got = [float(progs.loss(w, tokens, labels)),
               float(jax.jit(ref.stacked_loss, static_argnums=5)(
                   w["embed"], layers, w["norm"], tokens, labels, d))]
    np.testing.assert_allclose(got, [want, want], rtol=1e-6)


def test_control_losses_at_its_weights_start_at_the_references():
    """``train_round`` with rounded operands also gives the float32 loss
    of the weights each of its steps starts from: at the first step those
    are the seed's weights, so it is the float32 round's first loss; the
    control's own losses differ from it."""
    import jax
    import numpy as np
    from bench.reference import mamba2 as ref
    spec = smoke_spec("float32")
    sim = hfl.Sim(spec["config"], spec["traffic"], SEED, print)
    sim.tokens = sim.draw_tokens()
    batches = sim.reference_batches(0)
    run = lambda bits: ref.train_round(sim.weight_seed, sim.dims, sim.hp,
                                       batches, jax.devices(), bits)
    losses, at_weights, _ = run(3)
    exact, same, _ = run(None)
    assert same is exact and at_weights.shape == losses.shape
    np.testing.assert_allclose(at_weights[0], exact[0], rtol=1e-6)
    assert np.all(np.abs(losses[0] - exact[0]) > 1e-6 * exact[0])


def test_reference_weights_are_the_programs():
    """The reference rebuilds, layer by layer, the weights the benchmark
    hands the program in one call: the same random bits, up to one
    rounding of the scaling that XLA fuses differently in the two
    programs."""
    import jax
    import numpy as np
    from bench.reference import mamba2 as ref
    d = ref.Dims.of(smoke_spec("float32")["config"]["model"])
    program = hfl.reference_layout(
        hfl.program_params(jax.random.PRNGKey(5), d), d)
    rebuilt = ref.weights(5, d, jax.devices()[0])
    for a, b in zip(jax.tree.leaves(program), jax.tree.leaves(rebuilt)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)


@pytest.mark.parametrize("bits", [7, 3])
def test_rounded_operands_keep_that_many_bits(bits):
    """The control's rounding: nearest at ``bits`` mantissa bits, the
    exponent range untouched; at 7 bits it is bfloat16's rounding."""
    import jax.numpy as jnp
    import numpy as np
    from bench.reference import mamba2 as ref
    x = jnp.asarray(np.random.default_rng(0).normal(size=4096) * 1e-30,
                    jnp.float32)
    r = np.asarray(ref._round_mantissa(x, bits))
    assert np.all(np.abs(r - np.asarray(x)) <= np.abs(x) * 2.0 ** -(bits + 1))
    assert np.all(np.asarray(r).view(np.uint32) % 2 ** (23 - bits) == 0)
    if bits == 7:
        np.testing.assert_array_equal(
            r, np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)))


def recurrence(x, dt, A, B, C):
    """The SSD token by token: h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,
    y_t = h_t C_t."""
    import jax
    import jax.numpy as jnp

    def token(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t * A)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)
    heads, p, n = x.shape[1], x.shape[2], B.shape[2]
    return jax.lax.scan(token, jnp.zeros((heads, p, n)), (x, dt, B, C))[1]


def test_quadratic_ssd_is_the_recurrence():
    """The reference's SSD (the quadratic form, heads in groups) and its
    gradients equal the token-by-token recurrence's, to float32 rounding:
    both sum the same terms (5e-6 of the largest value read)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench.reference import mamba2 as ref
    rng = np.random.default_rng(1)
    length, heads, p, n = 96, 20, 8, 12           # two head groups, one short
    x, B, C = (jnp.asarray(rng.normal(size=s), jnp.float32) for s in
               ((length, heads, p), (length, heads, n), (length, heads, n)))
    dt = jnp.asarray(rng.uniform(1e-3, 0.3, (length, heads)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, heads), jnp.float32)
    g = jnp.asarray(rng.normal(size=(length, heads, p)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for f in (ref.ssd, recurrence):
            y, vjp = jax.vjp(f, x, dt, A, B, C)
            if f is ref.ssd:
                want = (y, vjp(g))
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves((y, vjp(g)))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5 * np.abs(b).max())
