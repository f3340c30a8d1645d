"""benchmark/reference/cca_moe_decoder_f32.py held to the program's `model.apply` at toy size on the CPU, built from the
cell's own YAML (cut by `tests/benchmark/toy_cca_moe.py`) through the component factory, from the benchmark's own seeded
weights; its attention in blocks held to the mask written out whole; its int8 control and its five variants shown to be
other models; and its training (AdamW without kept moments, the selection bias's rule after each step) held to the same
mathematics written the ordinary way.

Tolerance of the forward pass: the program computes its blocks in bfloat16 whatever the weights' type, the reference in
float32; with logits of standard deviation 0.25 at this size the two differ by about 0.01, so 0.03 holds the program.
tests/models/test_cca_moe.py holds the float32 program to 1e-5 and every leaf's gradient to 3e-4."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml
from pydantic import BaseModel

from benchmark.reference import cca_moe_decoder_f32 as reference
from benchmark.weights_cca_moe import CcaMoEShape, make_program_tree, seed_key
from benchmark.weights_hybrid import resolved
from tests.benchmark.toy import REPO, TOY_SEQ
from tests.benchmark.toy_cca_moe import CONFIG, TOY_HELD, TOY_LAYERS, TOY_OFFSET, shrink

SEED = 2**31 + 78
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
    return model, CcaMoEShape.from_yaml(raw)


@pytest.fixture(scope="module")
def batches(toy_model):
    rng = np.random.default_rng(3)
    streams = [rng.integers(0, toy_model[1].vocab_size - 1, size=(2, 65)) for _ in range(3)]
    return [(s[:, :-1], s[:, 1:]) for s in streams]


def test_reference_logits_agree_with_model_apply(toy_model):
    from flax.core import meta

    model, shape = toy_model
    assert (shape.n_layer, shape.experts_held, shape.expert_offset, shape.router_width, shape.rotated) == (TOY_LAYERS, TOY_HELD, TOY_OFFSET, 9, 16)
    like = jax.eval_shape(lambda: meta.unbox(model.init_params(jax.random.PRNGKey(0))))
    params = make_program_tree(shape, SEED, like, match_dtypes=False)
    tokens = np.random.default_rng(0).integers(0, shape.vocab_size - 1, size=(2, TOY_SEQ)).astype(np.int32)
    program = np.asarray(jax.jit(lambda p, t: model.apply(p, {model.sample_key: t})[model.prediction_key])(params, jnp.asarray(tokens)), np.float32)
    want = np.asarray(reference.logits_layer_by_layer(shape, SEED, tokens))
    assert want.std() > 0.1, "logits of some size, or the comparison says nothing"
    assert np.abs(program - want).max() < 0.03
    control = np.asarray(reference.logits_layer_by_layer(shape, SEED, tokens, "int8"))
    assert 0.002 < np.abs(control - want).max() < 0.08, "int8 kernels move the logits, a little"
    with pytest.raises(ValueError, match="unknown precision"):
        reference.logits_layer_by_layer(shape, SEED, tokens, "int4")


def test_attention_in_blocks_is_the_causal_mask_written_out_whole():
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(TOY_SEQ, h, 32)), jnp.float32) for h in (4, 2, 2))
    i, j = np.arange(TOY_SEQ)[:, None], np.arange(TOY_SEQ)[None, :]
    scores = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, 2, axis=1), precision="highest") / np.sqrt(32)
    probs = jax.nn.softmax(jnp.where((j <= i)[None], scores, -jnp.inf), axis=-1)
    want = jnp.einsum("hqk,khd->qhd", probs, jnp.repeat(v, 2, axis=1), precision="highest")
    for block in (16, 48, 512):  # rows in blocks that divide the sequence, that do not, and one block
        reference.Q_BLOCK, kept = block, reference.Q_BLOCK
        try:
            got = reference.attention_core(q, k, v)
        finally:
            reference.Q_BLOCK = kept
        assert float(jnp.abs(got - want).max()) < 1e-5, block


def test_the_rule_moves_the_bias_of_every_column_by_the_sign_of_its_loads_error():
    load = jnp.asarray([10.0, 0.0, 5.0, 5.0, 30.0, 1.0, 2.0, 3.0, 4.0])  # mean 6.67: the skip column (the last) is a column like the others
    moved = reference.moved_bias(jnp.zeros(9), load, 0.01)
    assert np.asarray(moved).tolist() == pytest.approx([-0.01, 0.01, 0.01, 0.01, -0.01, 0.01, 0.01, 0.01, 0.01])
    from modalities_tpu.models.gpt2.moe import update_selection_bias

    assert np.array_equal(np.asarray(update_selection_bias(jnp.zeros(9), load, 0.01)), np.asarray(moved)), "the program's rule"


def test_two_adamw_steps_without_kept_moments_are_adamw_with_them(toy_model, batches):
    """`train_steps` keeps no moments on the device; the same two steps with optax's AdamW (moments kept, the decay mask applied)
    and the bias moved by the rule from the loads `train_steps` counted (tests/models/test_cca_moe.py holds those to the
    program's) give the same losses and the same change of every leaf."""
    import optax

    shape, batches = toy_model[1], batches[:2]
    got = reference.train_steps(shape, SEED, batches, HYPER)
    params = reference.reference_params(shape, seed_key(SEED))
    seeded = params
    mask = {"runs": [{name: name in reference.DECAYED for name in params["runs"][0]}], "wte": False, "final_norm": False}
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-3, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, mask=mask))
    state, losses = tx.init(params), []
    grad = jax.jit(jax.value_and_grad(lambda p, tokens, targets: reference.batch_loss(p, tokens, targets, shape)))
    for step, (tokens, targets) in enumerate(batches):
        loss, grads = grad(params, jnp.asarray(tokens), jnp.asarray(targets))
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        params["runs"][0][reference.BIAS] = jax.vmap(lambda b, l: reference.moved_bias(b, l, shape.bias_update_speed))(
            params["runs"][0][reference.BIAS], jnp.asarray(got["loads"][step], jnp.float32))
        losses.append(float(loss))
    assert got["losses"] == pytest.approx(losses, rel=2e-5) and len(got["loads"]) == 2 and got["loads"][0].shape == (shape.n_layer, 9)
    moved = reference.leaf_norms(jax.tree.map(lambda a, b: a - b, params, seeded))
    for name, want in moved.items():
        np.testing.assert_allclose(got["delta_norms"][name], np.asarray(want), rtol=5e-3, atol=1e-7, err_msg=name)
    assert 0 < got["skip_share"][0] < 0.5 and 0 < got["pairs_held"][0] < 128


def test_a_shape_the_weights_do_not_know_is_refused():
    raw = shrink(yaml.safe_load((REPO / "benchmark" / "configs" / CONFIG / "train.yaml").read_text()))
    raw["model_raw"]["config"]["moe_config"]["router"] = "matrix"
    with pytest.raises(ValueError, match="router is the MLP"):
        CcaMoEShape.from_yaml(raw)
    assert dataclasses.replace(CcaMoEShape.from_yaml(shrink(yaml.safe_load((REPO / "benchmark" / "configs" / CONFIG / "train.yaml").read_text()))), without=("no_eda",)).without == ("no_eda",)
