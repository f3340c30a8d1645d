"""benchmark/reference/moe_mla_decoder_f32.py held to the program's `model.apply` at toy
size on the CPU, from the benchmark's own seeded weights; and its training (the gradient
one layer at a time, AdamW without kept moments) held to the same mathematics written
the ordinary way: `jax.grad` of the whole model, moments kept.

Tolerance of the forward pass: the program computes its blocks in bfloat16 whatever the
weights' type, the reference in float32; with logits of standard deviation 0.25 at this
size the two differ by up to 0.008 (read on the CPU, PR 30), so 0.03 holds the program
and a dropped norm, rotary or shared expert (0.1 and more) does not pass. The int8 control
cannot be told from bfloat16 at toy size by this number, which is why `correct` rests on
what the chip shows at the cell's own size (PERF.md section 2).
tests/models/test_moe_mla.py holds the float32 program to 1e-5."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml
from pydantic import BaseModel

from benchmark.reference import moe_mla_decoder_f32 as reference
from benchmark.weights_hybrid import resolved
from benchmark.weights_moe import MoEMLAShape, make_program_tree, program_tree, reference_layout, seed_key
from tests.benchmark.toy import REPO, TOY_SEQ
from tests.benchmark.toy_moe import CONFIG, shrink

SEED = 2**31 + 77
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
    return model, MoEMLAShape.from_yaml(raw)


@pytest.fixture(scope="module")
def batches(toy_model):
    rng = np.random.default_rng(3)
    streams = [rng.integers(0, toy_model[1].vocab_size - 1, size=(2, 65)) for _ in range(3)]
    return [(s[:, :-1], s[:, 1:]) for s in streams]


def test_reference_logits_agree_with_model_apply(toy_model):
    from flax.core import meta

    model, shape = toy_model
    like = jax.eval_shape(lambda: meta.unbox(model.init_params(jax.random.PRNGKey(0))))
    params = make_program_tree(shape, SEED, like, match_dtypes=False)
    tokens = np.random.default_rng(0).integers(0, shape.vocab_size - 1, size=(2, TOY_SEQ)).astype(np.int32)
    program = np.asarray(jax.jit(lambda p, t: model.apply(p, {model.sample_key: t})[model.prediction_key])(params, jnp.asarray(tokens)), np.float32)
    want = np.asarray(reference.logits_layer_by_layer(shape, SEED, tokens))
    assert want.std() > 0.1, "logits of some size, or the comparison says nothing"
    assert np.abs(program - want).max() < 0.03
    control = np.asarray(reference.logits_layer_by_layer(shape, SEED, tokens, "int8"))
    assert 0.002 < np.abs(control - want).max() < 0.08, "int8 kernels move the logits, a little"


def test_gradient_layer_by_layer_is_jax_grad_of_the_whole_model(toy_model, batches):
    _, shape = toy_model
    params = reference.reference_params(shape, seed_key(SEED))
    tokens, targets = (jnp.asarray(v, jnp.int32) for v in batches[0])
    want_loss, want = jax.jit(jax.value_and_grad(functools.partial(reference.batch_loss, shape=shape)))(params, tokens, targets)
    layers = [jax.tree.map(lambda x: x[k], run) for run in params["runs"] for k in range(jax.tree.leaves(run)[0].shape[0])]
    loss, (per_layer, outer), loads = reference.loss_and_gradients(shape, layers, {name: params[name] for name in OUTER}, tokens, targets)
    assert loads.shape == (len(shape.kinds) - shape.first_k_dense_replace, shape.n_routed_experts)
    assert (loads.sum(axis=1) == tokens.size * shape.num_experts_per_tok).all(), "every pair lands on one of the router's experts"
    assert 0 < reference.pairs_held(shape, loads) < tokens.size * shape.num_experts_per_tok, "some pairs land on the held experts, not all"
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    got = reference.by_run(shape, per_layer, outer)
    for r, run in enumerate(want["runs"]):
        for name, value in run.items():
            assert float(jnp.abs(got[f"run{r}.{name}"] - value).max()) <= 1e-5 * float(jnp.abs(value).max()) + 1e-12, (r, name)
    for name in OUTER:
        assert float(jnp.abs(got[name] - want[name]).max()) <= 1e-5 * float(jnp.abs(want[name]).max()), name


@pytest.mark.parametrize("speed", [0.0, 0.01])
def test_three_adamw_steps_without_kept_moments_are_adamw_with_them(toy_model, batches, speed):
    """`train_steps` keeps no moments and computes earlier gradients again; the same three
    steps with moments kept and the decay mask applied give the same losses, first
    gradient and change of every leaf. With a `bias_update_speed` the selection bias moves
    after each step by the sign of each expert's load error, and by nothing else."""
    shape = dataclasses.replace(toy_model[1], bias_update_speed=speed)
    got = reference.train_steps(shape, SEED, batches, HYPER, keep_first_grad=True)

    params = start = reference.reference_params(shape, seed_key(SEED))
    loss_and_grad = jax.jit(jax.value_and_grad(functools.partial(reference.batch_loss, shape=shape)))
    mu, nu = jax.tree.map(jnp.zeros_like, params), jax.tree.map(jnp.zeros_like, params)
    losses = []
    for t, (tokens, targets) in enumerate(batches, start=1):
        loss, grads = loss_and_grad(params, jnp.asarray(tokens, jnp.int32), jnp.asarray(targets, jnp.int32))
        losses.append(float(loss))
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        grads = jax.tree.map(lambda g: g * jnp.minimum(1.0, HYPER["clip_norm"] / norm), grads)
        if t == 1:
            first = grads
        mu = jax.tree.map(lambda m, g: 0.9 * m + 0.1 * g, mu, grads)
        nu = jax.tree.map(lambda v, g: 0.95 * v + 0.05 * g * g, nu, grads)

        def update(path, p, m, v):
            step = (m / (1 - 0.9 ** t)) / (jnp.sqrt(v / (1 - 0.95 ** t)) + 1e-8)
            return p - 1e-3 * (step if str(path[-1].key) in reference.NOT_DECAYED else step + 0.1 * p)

        if speed:
            layers = [jax.tree.map(lambda x: x[k], run) for run in params["runs"] for k in range(jax.tree.leaves(run)[0].shape[0])]
            _, _, loads = reference.loss_and_gradients(shape, layers, {name: params[name] for name in OUTER}, tokens, targets)
        params = jax.tree_util.tree_map_with_path(update, params, mu, nu)
        if speed:
            moved = params["runs"][1]["router_bias"] + speed * np.sign(loads.mean(axis=1, keepdims=True) - loads)
            params = {**params, "runs": [params["runs"][0], {**params["runs"][1], "router_bias": jnp.asarray(moved, jnp.float32)}]}
    assert got["losses"] == pytest.approx(losses, rel=1e-6)
    want_first = jax.device_get(reference.leaf_norms(first))
    want_change = jax.device_get(reference.leaf_norms(jax.tree.map(lambda a, b: a - b, params, start)))
    for name in want_first:
        np.testing.assert_allclose(got["first_grad_norms"][name], want_first[name], rtol=1e-4)
        np.testing.assert_allclose(got["delta_norms"][name], want_change[name], rtol=1e-3)
    for ours, theirs in zip(jax.tree.leaves(got["first_grad"]), jax.tree.leaves(jax.device_get(first))):
        np.testing.assert_allclose(ours, theirs, rtol=0, atol=1e-3 * float(np.abs(theirs).max()) + 1e-12)
    # the selection bias has no gradient and no decay: it does not move; a norm's scale moves by the learning rate's three steps at most
    assert got["first_grad_norms"]["run1.router_bias"].max() == 0.0
    if speed:  # every expert off the mean load moved by the speed each step: three steps at most
        assert 0 < got["delta_norms"]["run1.router_bias"].max() <= 3 * speed * np.sqrt(shape.n_routed_experts) * 1.001
    else:
        assert got["delta_norms"]["run1.router_bias"].max() == 0.0
    assert got["delta_norms"]["run0.kv_a_norm"].max() <= 3e-3 * np.sqrt(shape.kv_lora_rank) * 1.001
    assert len(got["pairs_held"]) == 3 and all(p > 0 for p in got["pairs_held"])


def test_the_seed_is_an_argument_and_layers_are_the_stacked_weights(toy_model):
    _, shape = toy_model
    one = jax.jit(lambda key: program_tree(shape, key))
    a, b = one(seed_key(1)), one(seed_key(SEED))
    assert one._cache_size() == 1, "one compiled program serves every seed"
    assert not np.array_equal(np.asarray(a["params"]["wte"], np.float32), np.asarray(b["params"]["wte"], np.float32))
    stacked = reference_layout(b)["runs"]
    def same(stacked_leaf, single):
        """The same draws. Not bitwise: the normal's last float32 bit depends on the loop the compiler put it in (a layer
        of this model draws its experts in a loop of their own), and where it decides a bfloat16 rounding (one element in
        some ten thousand) the two differ by one step of bfloat16."""
        a, b = np.asarray(stacked_leaf, np.float32), np.asarray(single, np.float32)
        if stacked_leaf.dtype == jnp.float32:  # the router's two leaves are kept unrounded: the last bit itself
            return np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)
        assert np.mean(a != b) < 1e-3
        np.testing.assert_allclose(a, b, rtol=2.0**-7, atol=0)

    layer2 = reference.reference_layer(shape, seed_key(SEED), 2)  # the third layer: the second of the second run
    for name in ("kv_b_proj", "experts_W_2", "router", "router_bias", "shared_V"):
        same(stacked[1][name][1], layer2[name])
    layer0 = reference.reference_layer(shape, seed_key(SEED), 0)  # the dense layer
    same(stacked[0]["W"][0], layer0["W"])


def test_a_share_of_the_experts_holds_the_uncut_layers_tensors(toy_model):
    """A routed expert's tensors depend on its index among ALL the router's experts: the layer
    told to hold experts 2..5 gets what the layer that holds all 8 has there."""
    import dataclasses

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
    dense = {"params": {"blocks": {"block": {}}, "wte": like["params"]["wte"]}}  # a program without the expert layer builds another tree
    with pytest.raises(ValueError, match="do not fit"):
        make_program_tree(shape, 0, dense)
