"""benchmark/reference/dense_decoder_f32.py held to the program's `model.apply` at toy
size on the CPU, from the benchmark's own seeded weights.

Tolerance: the program computes its blocks in bfloat16 (8 bits of mantissa) whatever the
weights' type, the reference in float32; with logits of standard deviation 0.23 at this
size the two differ by up to 0.005 (read on the CPU, PR 23), so 0.02 holds the program and
a dropped rotary, norm or gate (differences of 0.1 and more) does not pass. The int8
control moves the same logits by 0.011: it cannot be told from bfloat16 at toy size by
this number, which is why `correct` rests on what the chip shows at the cells' own sizes
(PERF.md section 2) and this file only shows that the reference is the same function."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml
from pydantic import BaseModel

from benchmark.reference import dense_decoder_f32 as reference
from benchmark.weights import DecoderShape, make_program_tree, program_tree, seed_key
from tests.benchmark.toy import REPO, TOY_SEQ, _shrink_model

SEED = 2**31 + 77


@pytest.fixture(scope="module")
def toy_model():
    from modalities_tpu.config.component_factory import ComponentFactory
    from modalities_tpu.config.pydantic_if_types import PydanticModelIFType
    from modalities_tpu.registry.components import COMPONENTS
    from modalities_tpu.registry.registry import Registry

    raw = yaml.safe_load((REPO / "benchmark/configs/modalities-2p7b-d6/train.yaml").read_text())
    block = raw["model_raw"]
    _shrink_model(block["config"])
    keys = raw["settings"]["referencing_keys"]
    block["config"].update(sample_key=keys["sample_key"], prediction_key=keys["prediction_key"])

    class Holder(BaseModel):
        model: PydanticModelIFType

    model = ComponentFactory(Registry(COMPONENTS)).build_components({"model": block}, Holder).model
    return model, DecoderShape.from_model_config(block["config"])


def test_reference_logits_agree_with_model_apply(toy_model):
    from flax.core import meta

    model, shape = toy_model
    like = jax.eval_shape(lambda: meta.unbox(model.init_params(jax.random.PRNGKey(0))))
    params = make_program_tree(shape, SEED, like, match_dtypes=False)
    tokens = np.random.default_rng(0).integers(0, shape.vocab_size - 1, size=(2, TOY_SEQ)).astype(np.int32)
    program = np.asarray(model.apply(params, {model.sample_key: jnp.asarray(tokens)})[model.prediction_key], np.float32)
    want = np.asarray(reference.logits_layer_by_layer(shape, SEED, tokens))
    assert want.std() > 0.1, "logits of some size, or the comparison says nothing"
    assert np.abs(program - want).max() < 0.02
    control = np.asarray(reference.logits_layer_by_layer(shape, SEED, tokens, "int8"))
    assert 0.002 < np.abs(control - want).max() < 0.05, "int8 weights move the logits, a little"


def test_the_seed_is_an_argument_not_a_constant(toy_model):
    _, shape = toy_model
    one = jax.jit(lambda key: program_tree(shape, key))
    a, b = one(seed_key(1)), one(seed_key(SEED))
    assert one._cache_size() == 1, "one compiled program serves every seed"
    assert not np.array_equal(np.asarray(a["params"]["wte"]), np.asarray(b["params"]["wte"]))
    again = make_program_tree(shape, SEED, b)
    assert np.array_equal(np.asarray(again["params"]["wte"], np.float32), np.asarray(b["params"]["wte"], np.float32))


def test_layer_by_layer_weights_are_the_stacked_weights(toy_model):
    _, shape = toy_model
    stacked = program_tree(shape, seed_key(SEED))["params"]["blocks"]["block"]
    layer1 = reference.reference_layer(shape, seed_key(SEED), 1)
    np.testing.assert_array_equal(np.asarray(stacked["mlp"]["W_2"]["kernel"][1], np.float32), np.asarray(layer1["W_2"]))
    np.testing.assert_array_equal(np.asarray(stacked["attn"]["q_attn"]["kernel"][1], np.float32), np.asarray(layer1["q_attn"]))


def test_weights_refuse_a_tree_they_do_not_fit(toy_model):
    _, shape = toy_model
    like = jax.eval_shape(lambda: program_tree(shape, seed_key(0)))
    like["params"]["wte"] = jax.ShapeDtypeStruct((shape.vocab_size + 1, shape.n_embd), jnp.bfloat16)
    with pytest.raises(ValueError, match=r"\['params'\]\['wte'\]"):
        make_program_tree(shape, 0, like)


def test_int8_rounding_keeps_one_scale_per_output_channel():
    w = jnp.asarray(np.random.default_rng(0).normal(size=(64, 4, 8)).astype(np.float32))
    q = reference.fake_quant_int8(w, (0,))
    steps = np.asarray(q / (jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0))
    np.testing.assert_allclose(steps, np.round(steps), atol=1e-4)
    assert float(jnp.abs(q - w).max()) <= float(jnp.max(jnp.abs(w)) / 127.0 / 2) + 1e-6
