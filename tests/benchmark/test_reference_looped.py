"""benchmark/reference/looped_decoder_f32.py in its own right, at toy size on the CPU: its backward pass, walked by hand
a layer application at a time, and its AdamW without kept moments, held to the same mathematics written the ordinary
way (`jax.grad` of the whole model as one function of one parameter tree, moments kept); its exit distribution; what
its int8 control touches; and that it is plain: float32, `highest`, no import of the program.
tests/models/test_looped.py holds the program to it (every exit, the loss, every gradient leaf, two AdamW steps)."""

import dataclasses
import functools
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import looped_decoder_f32 as reference
from benchmark.weights_looped import KERNELS, NORMS, OUTER, LoopedShape, layer_weights, program_tree, reference_layout, seed_key

REPO = Path(__file__).resolve().parents[2]
SEED = 2**31 + 78
SHAPE = LoopedShape(vocab_size=512, n_layer=3, n_head=4, n_embd=128, ffn_hidden=256, total_ut_steps=4, beta=0.1, gate_std=0.02)  # the cells seed the gate at 0
HYPER = {"lr": [1e-3, 1e-3, 1e-3], "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0}


@pytest.fixture(scope="module")
def batches():
    rng = np.random.default_rng(7)
    return [(s[:, :-1], s[:, 1:]) for s in (rng.integers(0, 511, size=(2, 49)).astype(np.int32) for _ in range(3))]


def seeded(shape=SHAPE, precision="f32"):
    key = seed_key(SEED)  # jitted, as `train_steps` makes them: an eager draw rounds an entry in ten thousand another way
    layer = jax.jit(lambda key, i: reference.reference_layer(shape, key, i, precision))
    return [layer(key, jnp.int32(i)) for i in range(shape.n_layer)], jax.jit(lambda key: reference.reference_outer(shape, key, precision))(key)


def whole_loss(params, tokens, targets, shape):
    """The equations of the module's docstring as ONE function of one tree (`layers`: the leaves stacked over the layers)."""
    def one_row(row_tokens, row_targets):
        h = jnp.take(params["wte"], row_tokens, axis=0)
        exits = []
        for _ in range(shape.total_ut_steps):
            for l in range(shape.n_layer):
                h = reference.block_forward(h, jax.tree.map(lambda v: v[l], params["layers"]), shape)
            h = reference.rms_norm(h, params["final_norm"], shape.norm_eps)
            exits.append(h)
        exits = jnp.stack(exits)
        logits = jnp.einsum("tse,ev->tsv", exits, params["lm_head"], precision="highest")
        ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, row_targets[None, :, None], axis=-1)[..., 0]
        g = jax.nn.sigmoid(jnp.einsum("tse,e->ts", exits, params["gate_w"], precision="highest") + params["gate_b"])
        stay = jnp.cumprod(1 - g, axis=0)
        p = jnp.concatenate([g[:1], g[1:-1] * stay[:-2], stay[-2:-1]], axis=0)  # written with products, not log-sigmoids
        return jnp.sum((p * ce).sum(axis=0) + shape.beta * (p * jnp.log(p)).sum(axis=0))
    return sum(one_row(t, y) for t, y in zip(jnp.asarray(tokens), jnp.asarray(targets))) / np.size(tokens)


def one_tree(layers, outer):
    return {"layers": {name: jnp.stack([layer[name] for layer in layers]) for name in layers[0]}, **outer}


def test_gradient_walked_by_hand_is_jax_grad_of_the_whole_model(batches):
    layers, outer = seeded()
    tokens, targets = batches[0]
    loss, (layer_grads, outer_grads), counted = reference.loss_and_gradients(SHAPE, layers, outer, tokens, targets)
    want_loss, want = jax.value_and_grad(whole_loss)(one_tree(layers, outer), tokens, targets, SHAPE)
    assert abs(loss - float(want_loss)) < 1e-5
    for name in (*KERNELS, *NORMS):
        for l in range(SHAPE.n_layer):
            got, w = layer_grads[l][name], want["layers"][name][l]
            assert float(jnp.abs(w).max()) > 0 and float(jnp.abs(got - w).max() / jnp.abs(w).max()) < 1e-4, (name, l)
    for name in OUTER:
        assert float(jnp.abs(outer_grads[name] - want[name]).max() / jnp.abs(want[name]).max()) < 1e-4, name
    assert len(counted["exit_ce"]) == 4 and 1.0 <= counted["expected_exit"] <= 4.0 and 0.0 <= counted["gate_entropy"] <= np.log(4) + 1e-6


def test_the_exit_distribution_is_a_distribution_and_the_gates_own():
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(4, 9)) * 3, jnp.float32)
    log_p, p = reference.exit_distribution(logits)
    g = jax.nn.sigmoid(logits)
    assert np.allclose(p.sum(axis=0), 1.0, atol=1e-6)
    assert np.allclose(p[0], g[0], atol=1e-6) and np.allclose(p[2], g[2] * (1 - g[0]) * (1 - g[1]), atol=1e-6)
    assert np.allclose(p[3], (1 - g[0]) * (1 - g[1]) * (1 - g[2]), atol=1e-6), "the last exit takes what is left: its own gate is not read"
    saturated = reference.exit_distribution(jnp.asarray([[80.0], [-80.0], [0.0], [0.0]]))
    assert np.all(np.isfinite(saturated[0])) and float(saturated[1][0, 0]) == 1.0, "a saturated gate gives a small probability, not a NaN"
    one = reference.exit_distribution(jnp.zeros((1, 5)))
    assert np.all(np.asarray(one[1]) == 1.0), "one walk: one exit, whatever the gate says"
    loss, expected, entropy = reference.exit_terms(jnp.ones((4, 9)) * jnp.arange(1.0, 5.0)[:, None], logits, 0.0)
    assert np.allclose(loss, expected, atol=1e-6), "with beta 0 and CE_t = t the loss IS the expected exit"


def test_two_adamw_steps_without_kept_moments_are_adamw_with_them(batches):
    """The first clipped gradient waits on the host and the moments are rebuilt from it: the same numbers as optax's
    AdamW on one tree (clip by global norm, decay on the kernels and the head alone), to float32 rounding."""
    import optax

    layers, outer = seeded()
    params = one_tree(layers, outer)
    mask = {"layers": {name: name in KERNELS for name in params["layers"]}, **{name: name == "lm_head" for name in OUTER}}
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-3, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, mask=mask))
    state, losses, start = tx.init(params), [], params
    for tokens, targets in batches[:2]:
        value, grads = jax.value_and_grad(whole_loss)(params, tokens, targets, SHAPE)
        updates, state = tx.update(grads, state, params)
        params, losses = optax.apply_updates(params, updates), losses + [float(value)]
    got = reference.train_steps(SHAPE, SEED, batches[:2], HYPER)
    assert np.allclose(got["losses"], losses, atol=1e-5)
    moved = jax.tree.map(lambda a, b: a - b, params, start)
    want = jax.device_get(reference.leaf_norms(moved))
    for name, norms in got["delta_norms"].items():
        assert np.all(np.asarray(norms) > 0) and np.allclose(norms, want[name], rtol=2e-3), name
    assert len(got["exit_ce"]) == 2 and len(got["exit_ce"][0]) == 4 and len(got["expected_exit"]) == 2


def test_the_int8_control_rounds_the_kernels_and_leaves_the_gate_and_the_norms():
    (layers, outer), (q_layers, q_outer) = seeded(), seeded(precision="int8")
    for name in KERNELS:
        assert 0 < float(jnp.abs(layers[0][name] - q_layers[0][name]).max()) < 0.02 * float(jnp.abs(layers[0][name]).max())
    for name in ("wte", "lm_head"):
        assert float(jnp.abs(outer[name] - q_outer[name]).max()) > 0
    for name in NORMS:
        assert bool((layers[0][name] == q_layers[0][name]).all())
    assert bool((outer["gate_w"] == q_outer["gate_w"]).all()) and bool((outer["final_norm"] == q_outer["final_norm"]).all())


def test_the_seed_is_an_argument_and_the_programs_tree_holds_the_same_tensors():
    key = seed_key(SEED)
    tree = reference_layout(jax.jit(functools.partial(program_tree, SHAPE))(key))
    for l in range(SHAPE.n_layer):
        for name, value in jax.jit(lambda key, l: layer_weights(SHAPE, key, l))(key, jnp.int32(l)).items():
            assert bool((tree["layers"][name][l] == value).all()), (name, l)
    other = reference_layout(jax.jit(functools.partial(program_tree, SHAPE))(seed_key(SEED + 1)))
    assert not bool((tree["layers"]["W"] == other["layers"]["W"]).all())
    assert tree["gate_w"].shape == (128,) and tree["gate_b"].shape == () and tree["gate_w"].dtype == jnp.float32
    assert float(jnp.abs(tree["gate_w"]).max()) > 0 and float(jnp.abs(reference_layout(program_tree(dataclasses.replace(SHAPE, gate_std=0.0), key))["gate_w"]).max()) == 0
    more_walks = reference_layout(jax.jit(functools.partial(program_tree, dataclasses.replace(SHAPE, total_ut_steps=7)))(key))
    assert all(bool((a == b).all()) for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(more_walks))), "no walk has a tensor of its own"


def test_the_reference_is_plain():
    text = (REPO / "benchmark" / "reference" / "looped_decoder_f32.py").read_text()
    imports = re.findall(r"^(?:from|import)\s+([\w.]+)", text, flags=re.M)
    assert not [m for m in imports if m.startswith("modalities_tpu")], "no import of the program under test"
    assert set(imports) <= {"__future__", "functools", "time", "jax", "jax.numpy", "numpy", "benchmark.weights_looped"}
    assert 'HIGHEST = "highest"' in text and "pallas" not in text
    einsums = re.findall(r"jnp\.einsum\((.*)\)", text)
    assert einsums and all("precision=HIGHEST" in call for call in einsums), "every matmul at `highest` precision"
