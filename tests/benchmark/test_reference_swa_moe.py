"""benchmark/reference/swa_moe_decoder_f32.py held to the program's `model.apply` at toy size on the CPU, from the
benchmark's own seeded weights; and its training (the gradient one layer at a time with the balance term's cotangent
beside the activation's, AdamW without kept moments) held to the same mathematics written the ordinary way: `jax.grad`
of the whole model, moments kept.

Tolerance of the forward pass: the program computes its blocks in bfloat16 whatever the weights' type, the reference
in float32; with logits of standard deviation 0.25 at this size the two differ by up to 0.01 (read on the CPU, PR 38),
so 0.03 holds the program. tests/models/test_swa_moe.py holds the float32 program to 1e-5, and shows there that a
dropped window or a plain rotary on the global layers is another model."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml
from pydantic import BaseModel

from benchmark.reference import swa_moe_decoder_f32 as reference
from benchmark.weights_hybrid import resolved
from benchmark.weights_swa_moe import Rotary, SwaMoEShape, make_program_tree, program_tree, reference_layout, seed_key
from tests.benchmark.toy import REPO, TOY_SEQ
from tests.benchmark.toy_swa_moe import CONFIG, shrink

SEED = 2**31 + 78
OUTER = ("wte", "lm_head", "final_norm")
HYPER = {"lr": [1e-3, 1e-3, 1e-3], "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0}


@pytest.fixture(scope="module")
def toy_model():
    from modalities_tpu.config.component_factory import ComponentFactory
    from modalities_tpu.config.pydantic_if_types import PydanticModelIFType
    from modalities_tpu.registry.components import COMPONENTS
    from modalities_tpu.registry.registry import Registry

    raw = shrink(yaml.safe_load((REPO / "benchmark" / "configs" / CONFIG / "train.yaml").read_text()))
    raw["model_raw"]["config"]["sequence_length"] = TOY_SEQ
    keys = raw["settings"]["referencing_keys"]
    raw["model_raw"]["config"].update(sample_key=keys["sample_key"], prediction_key=keys["prediction_key"])
    block = resolved(raw["model_raw"], raw)

    class Holder(BaseModel):
        model: PydanticModelIFType

    model = ComponentFactory(Registry(COMPONENTS)).build_components({"model": block}, Holder).model
    return model, SwaMoEShape.from_yaml(raw)


@pytest.fixture(scope="module")
def batches(toy_model):
    rng = np.random.default_rng(3)
    streams = [rng.integers(0, toy_model[1].vocab_size - 1, size=(2, 65)) for _ in range(3)]
    return [(s[:, :-1], s[:, 1:]) for s in streams]


def test_reference_logits_agree_with_model_apply(toy_model):
    from flax.core import meta

    model, shape = toy_model
    assert shape.kinds == ("swa", "swa", "swa", "attn") and shape.sliding_window == 32 < TOY_SEQ and shape.head_dim == 48
    like = jax.eval_shape(lambda: meta.unbox(model.init_params(jax.random.PRNGKey(0))))
    params = make_program_tree(shape, SEED, like, match_dtypes=False)
    tokens = np.random.default_rng(0).integers(0, shape.vocab_size - 1, size=(2, TOY_SEQ)).astype(np.int32)
    program = np.asarray(jax.jit(lambda p, t: model.apply(p, {model.sample_key: t})[model.prediction_key])(params, jnp.asarray(tokens)), np.float32)
    want = np.asarray(reference.logits_layer_by_layer(shape, SEED, tokens))
    assert want.std() > 0.1, "logits of some size, or the comparison says nothing"
    assert np.abs(program - want).max() < 0.03
    control = np.asarray(reference.logits_layer_by_layer(shape, SEED, tokens, "int8"))
    assert 0.002 < np.abs(control - want).max() < 0.08, "int8 kernels move the logits, a little"


def test_the_window_and_the_scaled_rotary_are_in_the_reference(toy_model):
    """Attention in blocks of heads and rows against the mask written out whole; and both mechanisms move the logits."""
    _, shape = toy_model
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(TOY_SEQ, h, 48)), jnp.float32) for h in (4, 2, 2))
    i, j = np.arange(TOY_SEQ)[:, None], np.arange(TOY_SEQ)[None, :]
    for window, seen in ((32, (j <= i) & (i - j < 32)), (None, j <= i), (1, i == j), (TOY_SEQ + 5, j <= i)):
        scores = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, 2, axis=1), precision="highest") / np.sqrt(48)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        want = jnp.einsum("hqk,khd->qhd", probs, jnp.repeat(v, 2, axis=1), precision="highest")
        for block in (16, 48, 512):  # rows in blocks that divide the sequence, that do not, and one block
            reference.Q_BLOCK, kept = block, reference.Q_BLOCK
            try:
                got = reference.attention_core(q, k, v, window)
            finally:
                reference.Q_BLOCK = kept
            assert float(jnp.abs(got - want).max()) < 1e-5, (window, block)
    tokens = rng.integers(0, shape.vocab_size - 1, size=(1, TOY_SEQ)).astype(np.int32)
    want = np.asarray(reference.logits_layer_by_layer(shape, SEED, tokens))
    no_window = dataclasses.replace(shape, sliding_window=2**30)
    plain = dataclasses.replace(shape, rotary=tuple((kind, Rotary("default", 500000.0)) for kind, _ in shape.rotary))
    for other in (no_window, plain):
        assert np.abs(np.asarray(reference.logits_layer_by_layer(other, SEED, tokens)) - want).max() > 1e-3
    assert reference.yarn_bounds(128, dataclasses.replace(shape.rotary_of("attn"), original=8192)) == (18, 35)


def test_gradient_layer_by_layer_is_jax_grad_of_the_whole_model(toy_model, batches):
    _, shape = toy_model
    shape = dataclasses.replace(shape, router_aux_loss_coef=0.5)  # heavy enough that a lost cotangent of the term would show
    params = reference.reference_params(shape, seed_key(SEED))
    tokens, targets = (jnp.asarray(v, jnp.int32) for v in batches[0])
    want_loss, want = jax.jit(jax.value_and_grad(functools.partial(reference.batch_loss, shape=shape)))(params, tokens, targets)
    layers = [jax.tree.map(lambda x: x[k], run) for run in params["runs"] for k in range(jax.tree.leaves(run)[0].shape[0])]
    loss, (per_layer, outer), (ce, aux, loads) = reference.loss_and_gradients(shape, layers, {name: params[name] for name in OUTER}, tokens, targets)
    assert loads.shape == (shape.n_layer, shape.n_routed_experts)
    assert (loads.sum(axis=1) == tokens.size * shape.num_experts_per_tok).all(), "every pair lands on one of the router's experts"
    assert 0 < reference.pairs_held(shape, loads) < tokens.size * shape.num_experts_per_tok, "some pairs land on the held experts, not all"
    assert loss == pytest.approx(float(want_loss), rel=1e-6) and loss == pytest.approx(ce + 0.5 * aux, rel=1e-6) and 1.0 <= aux < 4.0
    got = reference.by_run(shape, per_layer, outer)
    for r, run in enumerate(want["runs"]):
        for name, value in run.items():
            assert float(jnp.abs(got[f"run{r}.{name}"] - value).max()) <= 1e-4 * float(jnp.abs(value).max()) + 1e-12, (r, name)
    for name in OUTER:
        assert float(jnp.abs(got[name] - want[name]).max()) <= 1e-4 * float(jnp.abs(want[name]).max()), name
    # the term's gradient reaches the router, and only through the mean scores: without it the router's gradient is another
    no_term = jax.jit(jax.grad(functools.partial(reference.batch_loss, shape=dataclasses.replace(shape, router_aux_loss_coef=0.0))))(params, tokens, targets)
    router, without = want["runs"][0]["router"], no_term["runs"][0]["router"]
    assert float(jnp.abs(router - without).max()) > 0.1 * float(jnp.abs(router).max())


def test_three_adamw_steps_without_kept_moments_are_adamw_with_them(toy_model, batches):
    """`train_steps` keeps no moments on the device; the same three steps with moments kept and the decay mask applied
    give the same losses, balance terms, first gradient and change of every leaf."""
    shape = dataclasses.replace(toy_model[1], router_aux_loss_coef=0.02)
    got = reference.train_steps(shape, SEED, batches, HYPER, keep_first_grad=True)

    params = start = reference.reference_params(shape, seed_key(SEED))
    loss_and_grad = jax.jit(jax.value_and_grad(functools.partial(reference.batch_loss, shape=shape, with_parts=True), has_aux=True))
    mu, nu = jax.tree.map(jnp.zeros_like, params), jax.tree.map(jnp.zeros_like, params)
    losses, terms = [], []
    for t, (tokens, targets) in enumerate(batches, start=1):
        (loss, (_, aux)), grads = loss_and_grad(params, jnp.asarray(tokens, jnp.int32), jnp.asarray(targets, jnp.int32))
        losses.append(float(loss))
        terms.append(float(aux))
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        grads = jax.tree.map(lambda g: g * jnp.minimum(1.0, HYPER["clip_norm"] / norm), grads)
        if t == 1:
            first = grads
        mu = jax.tree.map(lambda m, g: 0.9 * m + 0.1 * g, mu, grads)
        nu = jax.tree.map(lambda v, g: 0.95 * v + 0.05 * g * g, nu, grads)

        def update(path, p, m, v):
            step = (m / (1 - 0.9 ** t)) / (jnp.sqrt(v / (1 - 0.95 ** t)) + 1e-8)
            return p - 1e-3 * (step if str(path[-1].key) in reference.NOT_DECAYED else step + 0.1 * p)

        params = jax.tree_util.tree_map_with_path(update, params, mu, nu)
    assert got["losses"] == pytest.approx(losses, rel=1e-6) and got["aux_loss"] == pytest.approx(terms, rel=1e-5)
    assert [loss - 0.02 * aux for loss, aux in zip(got["losses"], got["aux_loss"])] == pytest.approx(got["ce"], rel=1e-6)
    want_first = jax.device_get(reference.leaf_norms(first))
    want_change = jax.device_get(reference.leaf_norms(jax.tree.map(lambda a, b: a - b, params, start)))
    for name in want_first:
        np.testing.assert_allclose(got["first_grad_norms"][name], want_first[name], rtol=1e-4)
        np.testing.assert_allclose(got["delta_norms"][name], want_change[name], rtol=1e-3)
    for ours, theirs in zip(jax.tree.leaves(got["first_grad"]), jax.tree.leaves(jax.device_get(first))):
        np.testing.assert_allclose(ours, theirs, rtol=0, atol=1e-3 * float(np.abs(theirs).max()) + 1e-12)
    assert got["delta_norms"]["run0.attention_norm"].max() <= 3e-3 * np.sqrt(shape.n_embd) * 1.001, "a norm's scale moves by the learning rate's three steps at most"
    assert len(got["pairs_held"]) == 3 and all(p > 0 for p in got["pairs_held"])


def test_the_seed_is_an_argument_and_layers_are_the_stacked_weights(toy_model):
    _, shape = toy_model
    one = jax.jit(lambda key: program_tree(shape, key))
    a, b = one(seed_key(1)), one(seed_key(SEED))
    assert one._cache_size() == 1, "one compiled program serves every seed"
    assert not np.array_equal(np.asarray(a["params"]["wte"], np.float32), np.asarray(b["params"]["wte"], np.float32))
    stacked = reference_layout(b)["runs"]
    assert [jax.tree.leaves(run)[0].shape[0] for run in stacked] == [3, 1], "a run for the three window layers, one for the global layer"

    def same(stacked_leaf, single):
        """The same draws. Not bitwise: the normal's last float32 bit depends on the loop the compiler put it in, and where
        it decides a bfloat16 rounding (one element in some ten thousand) the two differ by one step of bfloat16."""
        a, b = np.asarray(stacked_leaf, np.float32), np.asarray(single, np.float32)
        if stacked_leaf.dtype == jnp.float32:
            return np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)
        assert np.mean(a != b) < 1e-3
        np.testing.assert_allclose(a, b, rtol=2.0**-7, atol=0)

    layer2, layer3 = (reference.reference_layer(shape, seed_key(SEED), i) for i in (2, 3))  # the last window layer, and the global one
    for name in ("q_attn", "c_proj", "experts_W_2", "router"):
        same(stacked[0][name][2], layer2[name])
        same(stacked[1][name][0], layer3[name])


def test_a_share_of_the_experts_holds_the_uncut_layers_tensors(toy_model):
    """A routed expert's tensors depend on its index among ALL the router's experts: the layer told to hold experts
    4..7 gets what the layer that holds all 16 has there."""
    _, shape = toy_model
    whole = dataclasses.replace(shape, experts_held=shape.n_routed_experts, expert_offset=0)
    cut, uncut = (reference.reference_layer(s, seed_key(SEED), 1) for s in (shape, whole))
    lo, hi = shape.expert_offset, shape.expert_offset + shape.experts_held
    for name in ("experts_W", "experts_V", "experts_W_2"):
        np.testing.assert_array_equal(np.asarray(cut[name]), np.asarray(uncut[name][lo:hi]))
    np.testing.assert_array_equal(np.asarray(cut["router"]), np.asarray(uncut["router"]))


def test_weights_refuse_a_tree_they_do_not_fit(toy_model):
    _, shape = toy_model
    like = jax.eval_shape(lambda: program_tree(shape, seed_key(0)))
    like["params"]["wte"] = jax.ShapeDtypeStruct((shape.vocab_size + 1, shape.n_embd), jnp.bfloat16)
    with pytest.raises(ValueError, match=r"\['params'\]\['wte'\]"):
        make_program_tree(shape, 0, like)
    dense = {"params": {"blocks": {"block": {}}, "wte": like["params"]["wte"]}}  # a program without layer kinds builds another tree
    with pytest.raises(ValueError, match="do not fit"):
        make_program_tree(shape, 0, dense)
    with pytest.raises(ValueError, match="softmax"):
        raw = shrink(yaml.safe_load((REPO / "benchmark" / "configs" / CONFIG / "train.yaml").read_text()))
        raw["model_raw"]["config"]["moe_config"]["scoring_func"] = "sigmoid"
        SwaMoEShape.from_yaml(raw)
