"""A decoder whose layers are of two kinds (models/gpt2/ssm.py beside attention), held
to the plain reference (benchmark/reference/hybrid_ssm_decoder_f32.py) on the benchmark's
seeded weights at toy widths: a period of 4 layers with attention at index 2."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark.reference import hybrid_ssm_decoder_f32 as reference
from benchmark.weights_hybrid import HybridShape, make_program_tree, reference_layout, seed_key
from modalities_tpu.models.gpt2.gpt2_model import GPT2LLM, GPT2LLMConfig
from modalities_tpu.models.gpt2.ssm import MambaMixer, layer_kinds, layer_runs
from tests.models.test_gpt2_model import tiny_gpt2

SEED = 2**31 + 7
NORM = {"norm_type": "rms_norm", "config": {"ndim": 128, "bias": False, "epsilon": 1e-6}}
HYBRID = dict(
    sample_key="input_ids", prediction_key="logits", poe_type="NOPE", sequence_length=64, vocab_size=512, n_layer=4,
    n_head_q=4, n_head_kv=1, n_embd=128, ffn_hidden=384, dropout=0.0, bias=False,
    attention_config={"qkv_transforms": [{"type_hint": "IdentityTransform", "config": {}}]},
    attention_implementation="pytorch_flash", activation_type="swiglu", attention_norm_config=NORM, ffn_norm_config=NORM,
    lm_head_norm_config=NORM, use_weight_tying=True, attn_layer_period=4, attn_layer_offset=2,
    ssm_config={"d_state": 8, "dt_rank": 8},
)


@pytest.fixture(autouse=True)
def chunks_that_do_not_divide_the_sequence(monkeypatch):
    """The scan's chunk is a constant of the program (128 steps); 24 makes these sequences of 64 take three chunks."""
    monkeypatch.setattr("modalities_tpu.ops.selective_scan.CHUNK", 24)


def unboxed_shapes(model):
    return jax.eval_shape(lambda: meta.unbox(model.init_params(jax.random.PRNGKey(0))))


@pytest.fixture(scope="module")
def hybrid():
    """The model computing in float32, its seeded weights in float32, and their shape."""
    model = GPT2LLM(**HYBRID).with_spec_updates(compute_dtype="float32")
    shape = HybridShape.from_yaml({"model_raw": {"config": HYBRID}})
    params = make_program_tree(shape, SEED, unboxed_shapes(model), match_dtypes=False)
    return model, shape, jax.tree.map(lambda x: x.astype(jnp.float32), params)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 511, size=(2, 65)).astype(np.int32)


def logits_of(model, params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, t: model.apply(p, {"input_ids": t})["logits"])(params, jnp.asarray(tokens[:, :-1])), np.float32)


@pytest.fixture(scope="module")
def float32_logits(hybrid, tokens):
    model, _, params = hybrid
    return logits_of(model, params, tokens)


def test_layer_pattern_and_runs_from_the_two_published_keys():
    assert layer_kinds(28, 14, 7) == ("ssm",) * 7 + ("attn",) + ("ssm",) * 13 + ("attn",) + ("ssm",) * 6
    assert layer_runs(layer_kinds(14, 14, 7)) == (("ssm", 7), ("attn", 1), ("ssm", 6))
    assert layer_kinds(3, None, 0) == ("attn",) * 3
    spec = GPT2LLM(**HYBRID).config_spec
    assert spec.layer_kinds == ("ssm", "ssm", "attn", "ssm") and spec.runs == (("ssm", 2), ("attn", 1), ("ssm", 1))
    assert spec.ssm.d_inner == 256 and spec.has_ssm and hash(spec) == hash(GPT2LLM(**HYBRID).config_spec)


@pytest.mark.parametrize("changes, message", [
    ({"attn_layer_period": None}, "need attn_layer_period"),
    ({"ssm_config": None}, "give ssm_config"),
    ({"attn_layer_offset": 4}, "below attn_layer_period"),
])
def test_config_refuses_half_a_layer_pattern(changes, message):
    with pytest.raises(ValueError, match=message):
        GPT2LLMConfig(**{**HYBRID, **changes})


def test_parameter_tree_names_dtypes_and_mambas_initial_values():
    model = GPT2LLM(**HYBRID).with_spec_updates(param_dtype="bfloat16")
    params = meta.unbox(jax.jit(model.init_params)(jax.random.PRNGKey(0)))["params"]
    assert sorted(params) == ["lm_head_norm", "run_0", "run_1", "run_2", "wte"]
    ssm = params["run_0"]["blocks"]["block"]["ssm"]
    assert sorted(ssm) == ["A_log", "D", "b_norm", "c_norm", "conv", "dt_norm", "dt_proj", "in_proj", "out_proj", "x_proj"]
    assert "attn" in params["run_1"]["blocks"]["block"] and "ssm" not in params["run_1"]["blocks"]["block"]
    # the three large kernels in the parameter dtype; the scan's own leaves and the two small kernels with large values float32
    assert {ssm[k]["kernel"].dtype for k in ("in_proj", "x_proj", "out_proj")} == {jnp.dtype("bfloat16")}
    assert {ssm[k].dtype for k in ("A_log", "D")} | {ssm["conv"][k].dtype for k in ("kernel", "bias")} | {
        ssm["dt_proj"][k].dtype for k in ("kernel", "bias")} | {ssm["dt_norm"]["scale"].dtype} == {jnp.dtype("float32")}
    np.testing.assert_allclose(ssm["A_log"][0, 3], np.log(np.arange(1, 9)), rtol=1e-6)
    assert np.all(np.asarray(ssm["D"]) == 1.0)
    dt = np.log1p(np.exp(np.asarray(ssm["dt_proj"]["bias"])))  # softplus of the bias: the step sizes drawn
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01 and dt.std() > 0.01


@pytest.fixture(scope="module")
def reference_logits(hybrid, tokens):
    return np.asarray(reference.logits_layer_by_layer(hybrid[1], SEED, tokens[:, :-1]))


def test_float32_program_is_the_reference_forward(float32_logits, reference_logits):
    assert reference_logits.std() > 0.1, "logits of some size, or the comparison says nothing"
    assert np.abs(float32_logits - reference_logits).max() < 1e-5


def test_mixer_alone_is_the_references_mixer(hybrid):
    model, shape, params = hybrid
    u = jnp.asarray(np.random.default_rng(2).normal(size=(2, 64, 128)), jnp.float32)
    block = jax.tree.map(lambda x: x[1], params["params"]["run_0"]["blocks"]["block"]["ssm"])  # the run's second layer
    with jax.default_matmul_precision("highest"):
        got = MambaMixer(model.config_spec).apply({"params": block}, u)
    w = jax.tree.map(lambda x: x[1], reference_layout(params)["runs"][0])
    want = jax.vmap(lambda row: reference.ssm_mixer(row, w, shape))(u)
    assert float(jnp.abs(want).max()) > 1e-3, "an output of some size (out_proj is small at its scaled init)"
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < 1e-5


def test_bfloat16_program_against_the_reference(hybrid, tokens, reference_logits):
    """The program as the recipes run it: blocks compute in bfloat16 (8 bits of mantissa)
    whatever the weights' type. With logits of standard deviation 0.25 at this size the two
    differ by up to 0.006 (read on the CPU, PR 26); 0.02 holds that, and a dropped gate,
    norm or skip (0.1 and more) does not pass."""
    assert np.abs(logits_of(GPT2LLM(**HYBRID), hybrid[2], tokens) - reference_logits).max() < 0.02


@pytest.mark.parametrize("kernels", [False, True], ids=["plain_scan", "scan_kernels_interpreted"])
def test_loss_and_every_gradient_leaf_are_the_references(hybrid, tokens, monkeypatch, kernels):
    """With `kernels`, the scan as a TPU runs it: the Pallas kernels (interpreted), in every state-space layer."""
    model, shape, params = hybrid
    monkeypatch.setattr("modalities_tpu.ops.selective_scan.uses_kernels", lambda interpret=False: kernels)

    def loss(params):
        with jax.default_matmul_precision("highest"):
            logits = model.apply(params, {"input_ids": jnp.asarray(tokens[:, :-1])})["logits"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, jnp.asarray(tokens[:, 1:])[..., None], axis=-1))

    got_loss, got = jax.jit(jax.value_and_grad(loss))(params)
    want = reference.reference_params(shape, seed_key(SEED))
    layers = [jax.tree.map(lambda x: x[k], run) for run in want["runs"] for k in range(jax.tree.leaves(run)[0].shape[0])]
    want_loss, (per_layer, outer) = reference.loss_and_gradients(
        shape, layers, {"wte": want["wte"], "final_norm": want["final_norm"]}, tokens[:, :-1], tokens[:, 1:])
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    got, want = reference_layout(got), reference.by_run(shape, per_layer, outer)
    leaves = [(f"run{r}.{name}", got["runs"][r][name], want[f"run{r}.{name}"]) for r in range(3) for name in got["runs"][r]]
    leaves += [(name, got[name], want[name]) for name in ("wte", "final_norm")]
    assert len(leaves) == 2 * 17 + 9 + 2 == len(want)
    for name, g, w in leaves:
        assert float(jnp.abs(w).max()) > 0, name
        assert float(jnp.abs(g - w).max() / jnp.abs(w).max()) < 2e-4, name


def test_three_runs_give_the_logits_of_the_same_layers_unrolled(hybrid, tokens, float32_logits):
    _, _, params = hybrid
    unrolled = GPT2LLM(**HYBRID).with_spec_updates(compute_dtype="float32", scan_layers=False)
    p, flat, layer = params["params"], {}, 0
    for run in ("run_0", "run_1", "run_2"):
        stacked = p[run]["blocks"]["block"]
        for k in range(jax.tree.leaves(stacked)[0].shape[0]):
            flat[f"h_{layer}"] = jax.tree.map(lambda x: x[k], stacked)
            layer += 1
    flat.update(wte=p["wte"], lm_head_norm=p["lm_head_norm"])
    assert jax.tree.map(jnp.shape, {"params": flat}) == jax.tree.map(lambda s: s.shape, unboxed_shapes(unrolled))
    np.testing.assert_allclose(float32_logits, logits_of(unrolled, {"params": flat}, tokens), atol=2e-6)


def test_one_kind_of_layer_is_the_dense_decoder_bitwise():
    """A stack of one run keeps the dense decoder's tree, and a pattern that puts
    attention in every layer computes what the dense decoder computes, bit for bit."""
    dense = tiny_gpt2("pytorch_flash")
    params = meta.unbox(dense.init_params(jax.random.PRNGKey(0)))
    assert sorted(params["params"]) == ["blocks", "lm_head_norm", "wte"]
    assert sorted(params["params"]["blocks"]["block"]) == ["attention_norm", "attn", "ffn_norm", "mlp"]
    assert dense.config_spec.layer_kinds == () and dense.config_spec.runs == (("attn", 2),)
    every_layer = tiny_gpt2("pytorch_flash", attn_layer_period=1, attn_layer_offset=0, ssm_config={"d_state": 8})
    assert every_layer.config_spec.runs == (("attn", 2),)
    same = meta.unbox(every_layer.init_params(jax.random.PRNGKey(0)))
    assert jax.tree.structure(same) == jax.tree.structure(params)
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(jax.tree.leaves(same), jax.tree.leaves(params)))
    batch = {"input_ids": jnp.asarray(np.random.default_rng(0).integers(0, 127, size=(2, 32)), jnp.int32)}
    assert bool(jnp.array_equal(dense.apply(params, batch)["logits"], every_layer.apply(params, batch)["logits"]))


@pytest.mark.parametrize("entry", ["init_decode_cache", "init_slot_cache", "init_paged_cache"])
def test_serving_refuses_by_name_of_the_missing_cache(hybrid, entry):
    model, _, params = hybrid
    args = {"init_decode_cache": (params, 1), "init_slot_cache": (params, 2), "init_paged_cache": (params, 4, 16)}[entry]
    with pytest.raises(NotImplementedError, match="recurrent-state cache"):
        getattr(model, entry)(*args)


def test_weight_decay_leaves_out_what_mamba_marks_and_the_biases():
    from modalities_tpu.optimizers.optimizer_factory import build_weight_decay_mask

    model = GPT2LLM(**HYBRID)
    mask = build_weight_decay_mask(unboxed_shapes(model), model, ["embedding", "norm", "ssm"])
    ssm = mask["params"]["run_0"]["blocks"]["block"]["ssm"]
    assert not any((ssm["A_log"], ssm["D"], ssm["conv"]["bias"], ssm["dt_proj"]["bias"], ssm["dt_norm"]["scale"]))
    assert all((ssm["in_proj"]["kernel"], ssm["x_proj"]["kernel"], ssm["dt_proj"]["kernel"], ssm["out_proj"]["kernel"], ssm["conv"]["kernel"]))
    assert mask["params"]["run_1"]["blocks"]["block"]["attn"]["q_attn"]["kernel"] and not mask["params"]["wte"]
    # the reference's list of what is not decayed is the same set of leaves
    named = reference_layout(mask)
    for r, run in enumerate(named["runs"]):
        for name, decayed in run.items():
            assert decayed == (name not in reference.NOT_DECAYED), (r, name)


def test_mfu_calculator_counts_attention_only_where_a_layer_holds_it():
    from modalities_tpu.utils.mfu import GPT2MFUCalculator

    model = GPT2LLM(**HYBRID)
    hybrid_mfu = GPT2MFUCalculator(n_layer=4, sequence_length=64, n_embd=128, world_size=1, wrapped_model=model)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(unboxed_shapes(model)))
    assert hybrid_mfu.num_parameters == n and hybrid_mfu.n_attention_layer == 1
    assert hybrid_mfu.compute(1000.0) == pytest.approx(1000.0 * (6 * n + 12 * 1 * 64 * 128) / hybrid_mfu._peak)
    dense = GPT2MFUCalculator(n_layer=4, sequence_length=64, n_embd=128, world_size=1, num_parameters=n)
    assert dense.n_attention_layer == 4
