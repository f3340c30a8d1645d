"""benchmark/reference/ssd_moe_decoder_f32.py held to the program's `model.apply` at toy size on the CPU, from the
benchmark's own seeded weights; its training (the gradient one layer at a time with the balance term's cotangent beside the
activation's and the table's gradient from both its uses, AdamW without kept moments) held to the same mathematics written the
ordinary way: `jax.grad` of the whole model, moments kept; and the weights' shares held to the uncut layer's tensors.

Tolerance of the forward pass: the program computes its blocks in bfloat16 whatever the weights' type, the reference in
float32; with logits of standard deviation 0.014 at this size (the table at 0.02, the logits divided by 16) the two differ by up
to 0.0006 (read on the CPU, PR 52), so 0.002 holds the program. tests/models/test_ssd_moe.py holds the float32 program to 2e-5."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml
from pydantic import BaseModel

from benchmark.reference import ssd_moe_decoder_f32 as reference
from benchmark.weights_hybrid import resolved
from benchmark.weights_ssd_moe import SsdMoEShape, layer_weights, make_program_tree, program_tree, reference_layout, seed_key
from tests.benchmark.toy import REPO, TOY_SEQ
from tests.benchmark.toy_ssd_moe import CONFIG, shrink

SEED = 2**31 + 79
HYPER = {"lr": [1e-3, 1e-3], "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0}


def toy_yaml() -> dict:
    raw = shrink(yaml.safe_load((REPO / "benchmark" / "configs" / CONFIG / "train.yaml").read_text()))
    raw["settings"]["step_profile"]["sequence_length"] = TOY_SEQ
    return raw


@pytest.fixture(scope="module")
def toy_model():
    from modalities_tpu.config.component_factory import ComponentFactory
    from modalities_tpu.config.pydantic_if_types import PydanticModelIFType
    from modalities_tpu.registry.components import COMPONENTS
    from modalities_tpu.registry.registry import Registry

    raw = toy_yaml()

    class Holder(BaseModel):
        model: PydanticModelIFType

    model = ComponentFactory(Registry(COMPONENTS)).build_components({"model": resolved(raw["model_raw"], raw)}, Holder).model
    return model, SsdMoEShape.from_yaml(raw)


@pytest.fixture(scope="module")
def batches(toy_model):
    rng = np.random.default_rng(3)
    streams = [rng.integers(0, toy_model[1].vocab_size - 1, size=(2, 65)) for _ in range(2)]
    return [(s[:, :-1], s[:, 1:]) for s in streams]


def test_the_toy_is_the_configuration_cut_and_nothing_else(toy_model):
    _, shape = toy_model
    assert shape.kinds == ("ssd", "attn", "ssd") and (shape.heads, shape.heads_held, shape.head_dim, shape.state, shape.chunk) == (8, 2, 16, 16, 16)
    assert (shape.n_head_q_all, shape.n_head_kv_all, shape.n_head_q, shape.n_head_kv, shape.attn_head_dim) == (8, 4, 2, 1, 32)
    assert (shape.embedding_multiplier, shape.residual_multiplier, shape.attention_multiplier, shape.logits_scaling) == (12.0, 0.22, 1 / 32, 16.0)
    assert (shape.shared_width, shape.shared_shards, shape.shared_hidden, shape.experts_held, shape.expert_offset, shape.norm_eps) == (128, 4, 32, 4, 4, 1e-5)


def test_reference_logits_agree_with_model_apply(toy_model):
    from flax.core import meta

    model, shape = toy_model
    like = jax.eval_shape(lambda: meta.unbox(model.init_params(jax.random.PRNGKey(0))))
    params = make_program_tree(shape, SEED, like, match_dtypes=False)
    tokens = np.random.default_rng(1).integers(0, shape.vocab_size - 1, size=(2, 64)).astype(np.int32)
    got = model.apply(params, {model.sample_key: jnp.asarray(tokens)})[model.prediction_key]
    ref = reference.reference_params(shape, seed_key(SEED))

    def logits(params, row):
        x = reference.embed(params["wte"], row, shape)
        for (kind, _, length), stacked in zip(shape.runs, params["runs"]):
            for i in range(length):
                x = reference.block_forward(x, jax.tree.map(lambda leaf, i=i: leaf[i], stacked), kind, shape)[0]
        return jnp.einsum("se,ve->sv", reference.rms_norm(x, params["final_norm"], shape.norm_eps), params["wte"], precision="highest") / shape.logits_scaling

    want = jax.jit(jax.vmap(logits, in_axes=(None, 0)))(ref, jnp.asarray(tokens))
    assert got.shape == want.shape == (2, 64, shape.vocab_size) and 0.005 < float(jnp.std(want)) < 0.05
    assert float(jnp.abs(got - want).max()) < 0.002


def test_gradient_layer_by_layer_is_jax_grad_of_the_whole_model(toy_model, batches):
    _, shape = toy_model
    shape = dataclasses.replace(shape, router_aux_loss_coef=0.5)  # heavy enough that a lost cotangent of the term would show
    params = reference.reference_params(shape, seed_key(SEED))
    tokens, targets = (jnp.asarray(v, jnp.int32) for v in batches[0])
    want_loss, want = jax.jit(jax.value_and_grad(functools.partial(reference.batch_loss, shape=shape)))(params, tokens, targets)
    layers = [jax.tree.map(lambda x: x[k], run) for run in params["runs"] for k in range(jax.tree.leaves(run)[0].shape[0])]
    loss, (per_layer, outer), (ce, aux, loads) = reference.loss_and_gradients(shape, layers, {name: params[name] for name in reference.OUTER}, tokens, targets)
    assert loads.shape == (shape.n_layer, shape.n_routed_experts)
    assert (loads.sum(axis=1) == tokens.size * shape.num_experts_per_tok).all(), "every pair lands on one of the router's experts"
    assert 0 < reference.pairs_held(shape, loads) < tokens.size * shape.num_experts_per_tok, "some pairs land on the held experts, not all"
    assert loss == pytest.approx(float(want_loss), rel=1e-6) and loss == pytest.approx(ce + 0.5 * aux, rel=1e-6) and 1.0 <= aux < 4.0
    got = reference.by_run(shape, per_layer, outer)
    for r, run in enumerate(want["runs"]):
        for name, value in run.items():
            assert float(jnp.abs(got[f"run{r}.{name}"] - value).max()) <= 1e-4 * float(jnp.abs(value).max()) + 1e-12, (r, name)
    for name in reference.OUTER:  # the table's gradient holds both its uses: the head's, and the embedding's times its multiplier
        assert float(jnp.abs(got[name] - want[name]).max()) <= 1e-4 * float(jnp.abs(want[name]).max()), name


def test_two_adamw_steps_without_kept_moments_are_adamw_with_them(toy_model, batches):
    """`train_steps` keeps no moments on the device; the same two steps with moments kept and the decay mask applied
    give the same losses, balance terms, first gradient and change of every leaf."""
    shape = toy_model[1]
    got = reference.train_steps(shape, SEED, batches, HYPER, keep_first_grad=True)

    params = start = reference.reference_params(shape, seed_key(SEED))
    loss_and_grad = jax.jit(jax.value_and_grad(functools.partial(reference.batch_loss, shape=shape, with_parts=True), has_aux=True))
    mu, nu = jax.tree.map(jnp.zeros_like, params), jax.tree.map(jnp.zeros_like, params)
    losses, terms = [], []
    for t, (tokens, targets) in enumerate(batches, start=1):
        (loss, (_, aux, _)), grads = loss_and_grad(params, jnp.asarray(tokens, jnp.int32), jnp.asarray(targets, jnp.int32))
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
    assert [loss - shape.router_aux_loss_coef * aux for loss, aux in zip(got["losses"], got["aux_loss"])] == pytest.approx(got["ce"], rel=1e-6)
    want_first = jax.device_get(reference.leaf_norms(first))
    want_change = jax.device_get(reference.leaf_norms(jax.tree.map(lambda a, b: a - b, params, start)))
    for name in want_first:
        np.testing.assert_allclose(got["first_grad_norms"][name], want_first[name], rtol=1e-4)
        np.testing.assert_allclose(got["delta_norms"][name], want_change[name], rtol=1e-3)
    for ours, theirs in zip(jax.tree.leaves(got["first_grad"]), jax.tree.leaves(jax.device_get(first))):
        np.testing.assert_allclose(ours, theirs, rtol=0, atol=1e-3 * float(np.abs(theirs).max()) + 1e-12)
    assert len(got["pairs_held"]) == 2 and all(p > 0 for p in got["pairs_held"])


def test_the_seed_is_an_argument_and_a_share_holds_the_uncut_layers_tensors(toy_model):
    """One compiled program serves every seed; and what a chip holds a share of depends on the part's index among ALL the published
    parts: the Mamba-2 heads 2 and 3 of 8 (share 1 of 4), the query heads 2 and 3 of 8 on key/value head 1 of 4, the second slice of the
    shared expert's width and the experts 4 to 7 of 16 are the tensors the uncut layer has there; B's and C's are every share's alike."""
    _, shape = toy_model
    one = jax.jit(lambda key: program_tree(shape, key))
    a, b = one(seed_key(1)), one(seed_key(SEED))
    assert one._cache_size() == 1 and not np.array_equal(np.asarray(a["params"]["wte"], np.float32), np.asarray(b["params"]["wte"], np.float32))
    assert [jax.tree.leaves(run)[0].shape[0] for run in reference_layout(b)["runs"]] == [1, 1, 1]
    whole = dataclasses.replace(shape, heads_held=8, n_head_q=8, n_head_kv=4, shared_shards=1, experts_held=16, expert_offset=0)
    mine = dataclasses.replace(shape, share=1)
    f32 = lambda tree: {k: np.asarray(v, np.float32) for k, v in tree.items()}  # noqa: E731
    cut, uncut = f32(layer_weights(mine, seed_key(SEED), 0, "ssd")), f32(layer_weights(whole, seed_key(SEED), 0, "ssd"))
    heads = slice(2, 4)
    z, x, bc, dt = (lambda w, n: (w[:, : n * 16], w[:, n * 16: 2 * n * 16], w[:, 2 * n * 16: 2 * n * 16 + 32], w[:, 2 * n * 16 + 32:]))(cut["in_proj"], 2)
    Z, X, BC, DT = (lambda w, n: (w[:, : n * 16], w[:, n * 16: 2 * n * 16], w[:, 2 * n * 16: 2 * n * 16 + 32], w[:, 2 * n * 16 + 32:]))(uncut["in_proj"], 8)
    for part, whole_part in ((z, Z[:, 32:64]), (x, X[:, 32:64]), (bc, BC), (dt, DT[:, heads])):
        np.testing.assert_array_equal(part, whole_part)
    np.testing.assert_array_equal(cut["conv"], np.concatenate([uncut["conv"][:, 32:64], uncut["conv"][:, 128:]], axis=1))
    for name in ("A_log", "dt_bias", "D"):
        np.testing.assert_array_equal(cut[name], uncut[name][heads])
    np.testing.assert_array_equal(cut["out_proj"], uncut["out_proj"][32:64])
    np.testing.assert_array_equal(cut["experts_W"], uncut["experts_W"][4:8])
    np.testing.assert_array_equal(cut["router"], uncut["router"])
    cut, uncut = f32(layer_weights(mine, seed_key(SEED), 1, "attn")), f32(layer_weights(whole, seed_key(SEED), 1, "attn"))
    np.testing.assert_array_equal(cut["q_attn"], uncut["q_attn"][:, 2:4])
    np.testing.assert_array_equal(cut["k_attn"], uncut["k_attn"][:, 1:2])
    np.testing.assert_array_equal(cut["c_proj"], uncut["c_proj"][2:4])
    assert cut["shared_W"].shape == (128, 32) and not np.array_equal(cut["shared_W"], f32(layer_weights(shape, seed_key(SEED), 1, "attn"))["shared_W"])


def test_weights_refuse_a_tree_or_a_model_they_do_not_fit(toy_model):
    _, shape = toy_model
    like = jax.eval_shape(lambda: program_tree(shape, seed_key(0)))
    like["params"]["wte"] = jax.ShapeDtypeStruct((shape.vocab_size + 1, shape.n_embd), jnp.bfloat16)
    with pytest.raises(ValueError, match=r"\['params'\]\['wte'\]"):
        make_program_tree(shape, 0, like)
    for change, match in (({"use_weight_tying": False}, "the head is the table"), ({"logits_scaling": None}, "four multipliers"),
                          ({"poe_type": "ABSOLUTE"}, "no positions")):
        raw = toy_yaml()
        raw["model_raw"]["config"].update(change)
        with pytest.raises(ValueError, match=match):
            SsdMoEShape.from_yaml(raw)
    raw = toy_yaml()
    raw["model_raw"]["config"]["moe_config"]["shared_expert_gate"] = True
    with pytest.raises(ValueError, match="ungated shared expert"):
        SsdMoEShape.from_yaml(raw)
    with pytest.raises(ValueError, match="no such step"):
        reference.skip_flags("rotary")
