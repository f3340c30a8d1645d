"""A looped decoder (`loop_config`: the stack walked several times over ONE set of weights, the final norm closing
every walk, sandwich norms, an exit gate and the loss over all exits), held to the plain reference
(benchmark/reference/looped_decoder_f32.py) on the benchmark's seeded weights at toy widths: d 128, 4 heads of 32,
SwiGLU 256, 3 layers walked 4 times, vocabulary 512."""

import contextlib
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark.reference import looped_decoder_f32 as reference
from benchmark.weights_looped import OUTER, LoopedShape, make_program_tree, reference_layout, seed_key
from modalities_tpu.loss_functions import CLMCrossEntropyLoss, LoopedExitLoss, exit_counter_names
from modalities_tpu.models.gpt2 import gpt2_model
from modalities_tpu.models.gpt2.gpt2_model import GPT2LLM, GPT2LLMConfig
from modalities_tpu.optimizers.optimizer_factory import OptimizerFactory, build_weight_decay_mask
from modalities_tpu.optimizers.scheduler_factory import DummyLRScheduler
from modalities_tpu.running_env.device_mesh import get_device_mesh
from modalities_tpu.telemetry import Telemetry, set_active_telemetry
from modalities_tpu.training.train_step import TrainStepBuilder

SEED = 2**31 + 11
NORM = {"norm_type": "rms_norm", "config": {"ndim": 128, "bias": False, "epsilon": 1e-6}}
ROTARY = {"qkv_transforms": [{"type_hint": "RotaryTransform", "config": {"n_embd": 128, "n_head": 4, "base_freq": 1000000}}]}
DENSE = dict(
    sample_key="input_ids", prediction_key="logits", poe_type="NOPE", sequence_length=64, vocab_size=512, n_layer=3,
    n_head_q=4, n_head_kv=4, n_embd=128, ffn_hidden=384, dropout=0.0, bias=False, attention_config=ROTARY,
    attention_implementation="manual", activation_type="swiglu", attention_norm_config=NORM, ffn_norm_config=NORM,
    lm_head_norm_config=NORM, use_weight_tying=False, lm_head_chunk_size=32,
)
TOY = {**DENSE, "post_attention_norm_config": NORM, "post_ffn_norm_config": NORM, "loop_config": {"total_ut_steps": 4, "beta": 0.1}}
HYPER = {"lr": [1e-3, 1e-3], "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0}


def build(**changes) -> GPT2LLM:
    return GPT2LLM(**GPT2LLMConfig(**{**TOY, **changes}).model_dump())


def unboxed(model, abstract: bool = True):
    init = lambda: meta.unbox(model.init_params(jax.random.PRNGKey(0)))  # noqa: E731
    return jax.eval_shape(init) if abstract else init()


@pytest.fixture(scope="module")
def toy():
    """The model computing in float32, its seeded weights (bfloat16 values, held in float32), and their shape."""
    model = build().with_spec_updates(compute_dtype="float32")
    shape = dataclasses.replace(LoopedShape.from_yaml({"model_raw": {"config": TOY}}), gate_std=0.02)  # the cells seed the gate at 0: here the path through it is held
    params = jax.tree.map(lambda x: x.astype(jnp.float32), make_program_tree(shape, SEED, unboxed(model), match_dtypes=False))
    return model, shape, params


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 511, size=(2, 65)).astype(np.int32)


def exits_of(model, params, ids):
    with jax.default_matmul_precision("highest"):
        out, counted = jax.jit(lambda p, t: model.apply_counted(p, {"input_ids": t}, train=True, hidden=True))(params, jnp.asarray(ids))
    assert counted == {}
    return out


def program_loss(model, params, ids, targets, fused: bool = False):
    """The loss over the exits as the train step computes it, from the program's own pieces."""
    loss_fn = LoopedExitLoss(target_key="target_ids", prediction_key="logits")
    out, _ = model.apply_counted(params, {"input_ids": ids}, train=True, hidden=True)
    walks = out["exits"].shape[0]
    tiled = jnp.broadcast_to(targets[None], (walks, *targets.shape))
    if fused:
        rows = loss_fn.fused_row_losses(out["exits"], model.head_weight(params), tiled, interpret=True)
    else:
        rows = loss_fn.row_losses(jax.vmap(lambda h: model.head_logits(params, h))(out["exits"]), tiled)[0]
    return loss_fn.exit_loss(rows, out["gate_logits"], targets, beta=model.config_spec.loop.beta)


# ------------------------------------------------------------------ config and tree


def test_one_tree_whatever_the_walks(toy):
    model, shape, params = toy
    assert sorted(params["params"]) == ["blocks", "exit_gate", "lm_head", "lm_head_norm", "wte"]
    assert sorted(params["params"]["blocks"]["block"]) == ["attention_norm", "attn", "ffn_norm", "mlp", "post_attention_norm", "post_ffn_norm"]
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))  # noqa: E731
    counts = {t: count(unboxed(build(loop_config={"total_ut_steps": t}))) for t in (1, 2, 4, 7)}
    assert set(counts.values()) == {shape.all_params()} == {count(params)}, counts
    assert model.counted == {name: () for name in exit_counter_names(4)} and model.trains_on_exits
    assert hash(model.config_spec) == hash(build().with_spec_updates(compute_dtype="float32").config_spec)
    assert hash(model.config_spec) != hash(build(loop_config={"total_ut_steps": 3}).with_spec_updates(compute_dtype="float32").config_spec)


def test_one_walk_without_sandwich_norms_and_gate_is_the_dense_decoder(tokens):
    dense = GPT2LLM(**GPT2LLMConfig(**DENSE).model_dump())
    looped = GPT2LLM(**GPT2LLMConfig(**{**DENSE, "loop_config": {"total_ut_steps": 1, "exit_gate": False}}).model_dump())
    p_dense, p_looped = unboxed(dense, abstract=False), unboxed(looped, abstract=False)
    assert jax.tree.structure(p_dense) == jax.tree.structure(p_looped)
    assert all(bool((a == b).all()) for a, b in zip(jax.tree.leaves(p_dense), jax.tree.leaves(p_looped)))
    assert not looped.trains_on_exits and looped.counted == {}
    ids = {"input_ids": jnp.asarray(tokens[:, :-1])}
    assert bool((dense.apply(p_dense, ids)["logits"] == looped.apply(p_looped, ids)["logits"]).all())
    hidden = lambda m, p: m.apply_counted(p, ids, train=True, hidden=True)[0]  # noqa: E731
    assert bool((hidden(dense, p_dense) == hidden(looped, p_looped)).all())


@pytest.mark.parametrize("changes, match", [
    ({"loop_config": {"total_ut_steps": 4, "early_exit_threshold": 0.9}}, "cumulative distribution"),
    ({"attn_layer_period": 2, "ssm_config": {}}, "ONE run of equal dense-decoder layers"),
])
def test_what_is_not_written_is_refused_at_config_time(changes, match):
    with pytest.raises(ValueError, match=match):
        GPT2LLMConfig(**{**TOY, **changes})


def test_serving_and_a_pipeline_axis_refuse_by_the_name_of_what_is_missing(toy, tokens):
    model, _, params = toy
    with pytest.raises(NotImplementedError, match="cache entry for every walk AND layer"):
        model.init_decode_cache(params, 1)
    with pytest.raises(NotImplementedError, match="cache entry for every walk AND layer"):
        model.init_slot_cache(params, 2)
    piped = build().with_spec_updates(pipeline_axis="pp")
    with pytest.raises(NotImplementedError, match="stage plan that closes on itself"):
        piped.apply(params, {"input_ids": jnp.asarray(tokens[:, :-1])})


def test_the_exit_loss_needs_the_looped_model_and_the_looped_model_the_exit_loss(toy):
    def builder(model, loss_fn):
        opt = OptimizerFactory.get_adam_w(lr=1e-3, betas=(0.9, 0.95), eps=1e-8, weight_decay=0.1,
                                          weight_decay_groups_excluded=["norm", "embedding"], wrapped_model=model)
        return TrainStepBuilder(model=model, loss_fn=loss_fn, optimizer_spec=opt, scheduler_spec=DummyLRScheduler(name="dummy", optimizer=opt))

    with pytest.raises(ValueError, match="looped_exit_loss"):
        builder(build(), CLMCrossEntropyLoss(target_key="target_ids", prediction_key="logits")).build(seed=0, materialize=False)
    with pytest.raises(ValueError, match="looped_exit_loss"):
        builder(GPT2LLM(**GPT2LLMConfig(**DENSE).model_dump()), LoopedExitLoss(target_key="target_ids", prediction_key="logits")).build(seed=0, materialize=False)
    with pytest.raises(ValueError, match="lm_head_chunk_size"):
        builder(build(lm_head_chunk_size=None), LoopedExitLoss(target_key="target_ids", prediction_key="logits")).build(seed=0, materialize=False)


# ------------------------------------------------------------------ against the reference


def test_every_exit_is_the_references(toy, tokens):
    """Every exit's hidden state, its logits and its gate, program in float32 against the reference: 1e-5 of the
    largest value is float32 rounding through 12 layer applications (read: 2e-6)."""
    model, shape, params = toy
    out = exits_of(model, params, tokens[:, :-1])
    hidden, gates, outer = reference.exits_of(shape, SEED, tokens[:, :-1])
    assert out["exits"].shape == (4, 2, 64, 128) and out["gate_logits"].shape == (4, 2, 64)
    assert float(jnp.abs(out["exits"] - hidden).max() / jnp.abs(hidden).max()) < 1e-5
    assert float(jnp.abs(out["gate_logits"] - gates).max()) < 1e-5 and float(jnp.abs(gates).max()) > 0.05
    with jax.default_matmul_precision("highest"):
        logits = jax.vmap(lambda h: model.head_logits(params, h))(out["exits"])
        want = jnp.einsum("tnse,ev->tnsv", hidden, outer["lm_head"])
    assert float(want.std()) > 0.1 and float(jnp.abs(logits - want).max()) < 1e-5
    # evaluation and `apply` report the last exit
    last = model.apply(params, {"input_ids": jnp.asarray(tokens[:, :-1])})["logits"]
    assert float(jnp.abs(last - want[-1]).max()) < 1e-4
    assert not bool(jnp.allclose(hidden[0], hidden[3], atol=1e-2)), "the walks differ"


def test_bfloat16_program_is_near_the_reference(toy, tokens):
    """The program as it trains computes its blocks in bfloat16: about three digits a layer application. The exits are
    normed (entries of size 1): read up to 0.06 after 12 applications (CPU, PR 32); a dropped post-norm moves them by 1."""
    _, shape, params = toy
    hidden, _, _ = reference.exits_of(shape, SEED, tokens[:, :-1])
    assert float(jnp.abs(exits_of(build(), params, tokens[:, :-1])["exits"] - hidden).max()) < 0.15


def reference_gradients(shape, tokens):
    key = seed_key(SEED)  # jitted, as the program's tree is made
    layer = jax.jit(lambda key, i: reference.reference_layer(shape, key, i))
    layers = [layer(key, jnp.int32(i)) for i in range(shape.n_layer)]
    return reference.loss_and_gradients(shape, layers, jax.jit(lambda key: reference.reference_outer(shape, key))(key), tokens[:, :-1], tokens[:, 1:])


def test_loss_counters_and_every_gradient_leaf(toy, tokens):
    """Loss, what the step counts and every leaf of the gradient against the reference's, which walks its backward
    pass by hand: 2e-4 of a leaf's largest entry is float32 rounding through 12 applications and back."""
    model, shape, params = toy
    ids, targets = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])
    with jax.default_matmul_precision("highest"):
        (got_loss, counted), got = jax.jit(jax.value_and_grad(lambda p: program_loss(model, p, ids, targets), has_aux=True))(params)
    want_loss, (layer_grads, outer_grads), want_counted = reference_gradients(shape, tokens)
    assert abs(float(got_loss) - want_loss) < 1e-5
    assert np.allclose([float(counted[f"loop_exit_ce_{t}"]) for t in range(1, 5)], want_counted["exit_ce"], atol=1e-5)
    assert abs(float(counted["loop_expected_exit"]) - want_counted["expected_exit"]) < 1e-5 and 1.5 < want_counted["expected_exit"] < 2.2
    assert abs(float(counted["loop_gate_entropy"]) - want_counted["gate_entropy"]) < 1e-5
    got = reference_layout(got)
    leaves = [(f"layers.{name}[{i}]", got["layers"][name][i], layer[name]) for i, layer in enumerate(layer_grads) for name in layer]
    leaves += [(name, got[name], outer_grads[name]) for name in OUTER]
    assert len(leaves) == 3 * 11 + 5
    for name, g, w in leaves:
        assert float(jnp.abs(w).max()) > 0, name
        assert float(jnp.abs(g - w).max() / jnp.abs(w).max()) < 2e-4, name


def test_the_shared_gradient_is_the_sum_over_walks_of_an_untied_copys(toy, tokens):
    """Four copies of the weights, one a walk, give four gradients; the shared weights' gradient is their sum."""
    model, _, params = toy
    ids, targets = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])
    spec, inner = model.config_spec, params["params"]
    from modalities_tpu.models.components.layer_norms import build_norm
    from modalities_tpu.models.gpt2.gpt2_model import GPT2Block

    def untied_loss(copies):
        """The model written out with a parameter tree a walk (`copies`: blocks and final norm, stacked on a leading axis)."""
        x = jnp.take(inner["wte"], ids, axis=0)
        exits, gates = [], []
        for t in range(4):
            for l in range(3):
                layer = jax.tree.map(lambda v: v[t, l], copies["blocks"]["block"])
                x = GPT2Block(spec).apply({"params": layer}, x)
            x = build_norm(spec.lm_head_norm, "n").apply({"params": jax.tree.map(lambda v: v[t], copies["lm_head_norm"])}, x)
            exits.append(x)
            gates.append((x @ inner["exit_gate"]["kernel"])[..., 0] + inner["exit_gate"]["bias"][0])
        loss_fn = LoopedExitLoss(target_key="target_ids", prediction_key="logits")
        rows = loss_fn.row_losses(jnp.stack(exits) @ inner["lm_head"]["kernel"], jnp.broadcast_to(targets[None], (4, *targets.shape)))[0]
        return loss_fn.exit_loss(rows, jnp.stack(gates), targets, beta=0.1)[0]

    shared = {"blocks": inner["blocks"], "lm_head_norm": inner["lm_head_norm"]}
    with jax.default_matmul_precision("highest"):
        untied = jax.jit(jax.grad(untied_loss))(jax.tree.map(lambda v: jnp.broadcast_to(v[None], (4, *v.shape)), shared))
        tied = jax.jit(jax.grad(lambda p: program_loss(model, p, ids, targets)[0]))(params)["params"]
    for path, per_walk in jax.tree_util.tree_leaves_with_path(untied):
        want, got = per_walk.sum(axis=0), tied
        for part in path:
            got = got[part.key]
        assert float(jnp.abs(per_walk[0] - per_walk[3]).max()) > 0, "the walks' gradients differ"
        assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < 1e-4, jax.tree_util.keystr(path)


def test_two_adamw_steps_through_the_train_step(toy, tokens):
    """The program's own train step (the loss over the exits through `apply_counted(hidden=True)`, AdamW with the
    configuration's decay mask, clipping), chunked tier, beside the reference's two steps: losses to 1e-5, every leaf's
    change to 2% of the reference's (Adam's first steps are lr * sign-like: a leaf's change is a norm of +-1e-3
    entries, which float32 rounding of a tiny gradient flips for a few entries)."""
    from modalities_tpu.models.model import MixedPrecisionSpec

    _, shape, params = toy
    model = build().update_train_spec(mixed_precision=MixedPrecisionSpec(compute_dtype="float32"))  # the builder writes it into the spec
    rng = np.random.default_rng(5)
    batches = [(s[:, :-1], s[:, 1:]) for s in (rng.integers(0, 511, size=(2, 65)).astype(np.int32) for _ in range(2))]
    opt = OptimizerFactory.get_adam_w(lr=1e-3, betas=(0.9, 0.95), eps=1e-8, weight_decay=0.1,
                                      weight_decay_groups_excluded=["embedding", "norm", "exit_gate"], wrapped_model=model)
    mask = build_weight_decay_mask(params, model, ["embedding", "norm", "exit_gate"])
    assert not mask["params"]["exit_gate"]["kernel"] and not mask["params"]["blocks"]["block"]["post_ffn_norm"]["scale"] and mask["params"]["lm_head"]["kernel"]
    builder = TrainStepBuilder(model=model, loss_fn=LoopedExitLoss(target_key="target_ids", prediction_key="logits"), optimizer_spec=opt,
                               scheduler_spec=DummyLRScheduler(name="dummy", optimizer=opt), grad_clip_norm=1.0)
    fns = builder.build(seed=0)
    state = fns.app_state_handle.state.replace(params=jax.tree.map(jnp.array, params))
    losses, metrics = [], None
    with jax.default_matmul_precision("highest"):
        for ids, targets in batches:
            batch = fns.put_batch({"samples": {"input_ids": ids[None]}, "targets": {"target_ids": targets[None]}})
            state, metrics = fns.train_step(state, batch)
            losses.append(float(metrics["loss"]))
    want = reference.train_steps(shape, SEED, batches, HYPER)
    assert np.allclose(losses, want["losses"], atol=1e-5), (losses, want["losses"])
    assert np.allclose([float(metrics[f"counter/loop_exit_ce_{t}"]) for t in range(1, 5)], want["exit_ce"][1], atol=1e-5)
    assert abs(float(metrics["counter/loop_expected_exit"]) - want["expected_exit"][1]) < 1e-5
    start = make_program_tree(shape, SEED, unboxed(model), match_dtypes=False)
    moved = jax.tree.map(lambda a, b: a - b.astype(jnp.float32), state.params, start)
    got = jax.device_get(reference.leaf_norms(reference_layout(moved)))
    for name, norms in want["delta_norms"].items():
        assert np.all(norms > 0), name
        assert np.allclose(got[name], norms, rtol=0.02), (name, got[name], norms)


@pytest.mark.parametrize("chunk", [32, 48, 64])
def test_the_chunked_and_whole_tiers_give_the_fused_tiers_numbers(toy, tokens, chunk):
    """One step of the train step by each form of `_row_ce` off the chip (the chunked scan over two chunks, over one and a
    tail, the whole logits) against the step a TPU traces, its kernels interpreted (`T x B x S` rows of one fused call):
    loss, what the step counts and the gradient's norm to float32 rounding."""
    import contextlib

    from modalities_tpu.models.model import MixedPrecisionSpec
    from modalities_tpu.ops import tiers

    _, _, params = toy
    batch = {"samples": {"input_ids": tokens[None, :, :-1]}, "targets": {"target_ids": tokens[None, :, 1:]}}

    def one_step(fused, head_chunk):
        with tiers.interpreted_kernels() if fused else contextlib.nullcontext():
            return stepped(head_chunk)

    def stepped(head_chunk):
        model = build(lm_head_chunk_size=head_chunk).update_train_spec(mixed_precision=MixedPrecisionSpec(compute_dtype="float32"))
        opt = OptimizerFactory.get_adam_w(lr=1e-3, betas=(0.9, 0.95), eps=1e-8, weight_decay=0.1,
                                          weight_decay_groups_excluded=["embedding", "norm", "exit_gate"], wrapped_model=model)
        fns = TrainStepBuilder(model=model, loss_fn=LoopedExitLoss(target_key="target_ids", prediction_key="logits"), optimizer_spec=opt,
                               scheduler_spec=DummyLRScheduler(name="dummy", optimizer=opt)).build(seed=0)
        state = fns.app_state_handle.state.replace(params=jax.tree.map(jnp.array, params))
        with jax.default_matmul_precision("highest"):
            _, metrics = fns.train_step(state, fns.put_batch(batch))
        return {name: float(value) for name, value in metrics.items()}

    fused, other = one_step(True, 32), one_step(False, chunk)
    assert set(fused) == set(other) >= {"loss", "grad_norm", "counter/loop_exit_ce_1", "counter/loop_expected_exit"}
    for name, value in fused.items():
        assert abs(other[name] - value) <= 1e-5 * max(1.0, abs(value)), (name, value, other[name])


# ------------------------------------------------------------------ the backward written by hand (full remat)


def toy_loss(model, params, ids, targets):
    """What the model trains on: the loss over the exits with the gate, the last exit's cross entropy without."""
    if model.trains_on_exits:
        return program_loss(model, params, ids, targets)[0]
    hidden, _ = model.apply_counted(params, {"input_ids": ids}, train=True, hidden=True)
    logits = model.head_logits(params, hidden)
    return -jnp.take_along_axis(jax.nn.log_softmax(logits), targets[..., None], axis=-1).mean()


@contextlib.contextmanager
def loop_plans(folder):
    """A sink for what is traced inside; the list it yields holds the `loop_plan` events once the block is left."""
    telemetry, plans = Telemetry(output_folder_path=folder, watchdog_deadline_s=0), []
    previous = set_active_telemetry(telemetry)
    try:
        yield plans
    finally:
        set_active_telemetry(previous)
    plans += [e for e in map(json.loads, telemetry.sink_path.read_text().splitlines()) if e.get("name") == "loop_plan"]


def loss_and_gradient(params, tokens, variant, **changes):
    """Loss and gradient of the toy under a remat variant."""
    model = build(**changes).with_spec_updates(compute_dtype="float32", remat_variant=variant)
    ids, targets = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(lambda p: toy_loss(model, p, ids, targets)))(params)


@pytest.mark.parametrize("gate", [True, False], ids=["gate", "no_gate"])
@pytest.mark.parametrize("walks", [1, 2, 4])
def test_the_backward_written_by_hand_is_autodiffs(toy, tokens, walks, gate):
    """Under full remat the walks' backward is `_walks_in_place`'s rule (every application's weight gradient added into one
    accumulator at its layer's index); without remat it is autodiff's transpose of the scan of scans. Same forward, so the
    same loss; every leaf of the gradient, the final norm's and the gate's among them, to float32 rounding of another
    order of summation (read: 2e-6 of a leaf's largest entry)."""
    params = toy[2] if gate else {"params": {name: leaf for name, leaf in toy[2]["params"].items() if name != "exit_gate"}}
    loop = {"total_ut_steps": walks, "exit_gate": gate, "beta": 0.1}
    want_loss, want = loss_and_gradient(params, tokens, None, loop_config=loop)
    got_loss, got = loss_and_gradient(params, tokens, "full", loop_config=loop)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert jax.tree.structure(got) == jax.tree.structure(want) == jax.tree.structure(params)
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want), jax.tree.leaves(got)):
        name = jax.tree_util.keystr(path)
        if "exit_gate" in name and walks == 1:  # one exit takes the whole distribution whatever its gate says
            assert float(jnp.abs(w).max()) == float(jnp.abs(g).max()) == 0, name
            continue
        assert float(jnp.abs(w).max()) > 0, name
        assert float(jnp.abs(g - w).max() / jnp.abs(w).max()) < 1e-5, name


@pytest.mark.parametrize("variant", [None, "selective_op"])
def test_without_full_remat_the_transpose_stays_autodiffs(toy, tokens, tmp_path, monkeypatch, variant):
    """No remat and `selective_op` save residuals only autodiff knows: the hand-written rule, which recomputes a block
    from its input, is not built for them, and `loop_plan` says which form sums the shared gradient."""
    monkeypatch.setattr(gpt2_model, "_walks_in_place", lambda *args: pytest.fail("the hand-written backward is full remat's"))
    with loop_plans(tmp_path) as plans:
        loss, grads = loss_and_gradient(toy[2], tokens, variant)
    (plan,) = plans
    assert np.isfinite(float(loss)) and all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(grads))
    assert (plan["shared_gradient"], plan["shared_gradient_copies"]) == ("summed_by_walk", 2)
    assert plan["block_inputs_kept"] == (None if variant is None else 12)


def test_loop_plan_says_what_the_walks_keep_and_how_the_shared_gradient_is_summed(toy, tokens, tmp_path):
    """One event a traced shape: the walks and what the remat keeps (PR 32's fields), and since PR 37 which form sums
    the layers' gradient over the walks, how many copies of it live, and their bytes as one shard holds them."""
    _, shape, params = toy
    with loop_plans(tmp_path) as plans:
        loss_and_gradient(params, tokens, "full")
    stack = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(params["params"]["blocks"]))
    assert stack == 3 * 4 * shape.layer_params()
    assert [{k: v for k, v in e.items() if k not in ("event", "name", "rank", "ts")} for e in plans] == [{
        "walks": 4, "layers": 3, "applications": 12, "exit_gate": True, "block_inputs_kept": 12,
        "block_input_bytes": 12 * 2 * 64 * 128 * 4, "head_rows": 4 * 2 * 64,
        "shared_gradient": "in_place", "shared_gradient_copies": 1, "shared_gradient_bytes": stack,
    }]


def test_under_dp_shard_2_the_step_agrees_with_one_device_and_the_plan_counts_a_shards_bytes(toy, tokens, tmp_path):
    """The train step under full remat on a dp_shard 2 mesh of CPU devices beside one device: loss, the gradient's norm
    and every leaf after the update; the accumulator is sharded as the weights are, so a shard holds half its bytes
    (but for the norms' scales, which no axis splits)."""
    from modalities_tpu.models.model import MixedPrecisionSpec

    _, shape, params = toy
    batch = {"samples": {"input_ids": tokens[None, :, :-1]}, "targets": {"target_ids": tokens[None, :, 1:]}}

    def one_step(handle, folder):
        model = build().with_spec_updates(remat_variant="full").update_train_spec(mixed_precision=MixedPrecisionSpec(compute_dtype="float32"))
        opt = OptimizerFactory.get_adam_w(lr=1e-3, betas=(0.9, 0.95), eps=1e-8, weight_decay=0.1,
                                          weight_decay_groups_excluded=["embedding", "norm", "exit_gate"], wrapped_model=model)
        with loop_plans(folder) as plans:
            fns = TrainStepBuilder(model=model, loss_fn=LoopedExitLoss(target_key="target_ids", prediction_key="logits"), optimizer_spec=opt,
                                   scheduler_spec=DummyLRScheduler(name="dummy", optimizer=opt), mesh_handle=handle, grad_clip_norm=1.0).build(seed=0)
            state = fns.app_state_handle.state
            state = state.replace(params=jax.tree.map(lambda new, old: jax.device_put(jnp.array(new), old.sharding), params, state.params))
            with jax.default_matmul_precision("highest"):
                state, metrics = fns.train_step(state, fns.put_batch(batch))
        (plan,) = plans
        return jax.device_get(state.params), {name: float(value) for name, value in metrics.items()}, plan

    one, one_metrics, one_plan = one_step(get_device_mesh(device_type="cpu", world_size=1, data_parallel_shard_degree=1), tmp_path / "one")
    two, two_metrics, two_plan = one_step(get_device_mesh(device_type="cpu", world_size=2, data_parallel_shard_degree=2), tmp_path / "two")
    for name in ("loss", "grad_norm", "counter/loop_expected_exit"):
        assert two_metrics[name] == pytest.approx(one_metrics[name], rel=1e-5), name
    for (path, a), b, start in zip(jax.tree_util.tree_leaves_with_path(one), jax.tree.leaves(two), jax.tree.leaves(params)):
        # Adam's first step is lr * sign-like: rounding flips a few entries of a tiny gradient (as in the two-step test above)
        assert np.linalg.norm(a - b) < 0.02 * np.linalg.norm(a - np.asarray(start)), jax.tree_util.keystr(path)
    scales = 3 * 4 * 128 * 4  # a layer's four norms, float32
    assert one_plan["shared_gradient_bytes"] == 3 * 4 * shape.layer_params()
    assert two_plan["shared_gradient_bytes"] == (one_plan["shared_gradient_bytes"] - scales) // 2 + scales
    assert (two_plan["shared_gradient"], two_plan["shared_gradient_copies"]) == ("in_place", 1)


def test_dropout_keys_reach_the_recomputed_block_as_they_reached_the_forwards(toy):
    """Were the rate not 0: a key an application, and the backward's recomputed block draws the mask the forward's drew.
    The rule against autodiff of the same forward (`custom_vjp.fun`) under dropout 0.1, every cotangent; other keys, another answer."""
    model = build(dropout=0.1).with_spec_updates(compute_dtype="float32", remat_variant="full")
    inner = toy[2]["params"]
    stacked, shared = inner["blocks"]["block"], {name: inner[name] for name in ("lm_head_norm", "exit_gate")}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 128))
    walks = gpt2_model._walks_in_place(model.config_spec, False, jnp.float32)

    def gradients(fn, keys):
        def loss(stacked, shared, x):
            exits, gates = fn(stacked, shared, x, keys)
            return jnp.square(exits).mean() + (jnp.tanh(gates) * exits[..., 0]).mean()

        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(stacked, shared, x)

    keys = jax.random.split(jax.random.PRNGKey(3), (4, 3))
    got, want = gradients(walks, keys), gradients(walks.fun, keys)
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want), jax.tree.leaves(got)):
        assert float(jnp.abs(w).max()) > 0 and float(jnp.abs(g - w).max() / jnp.abs(w).max()) < 1e-5, jax.tree_util.keystr(path)
    other = gradients(walks, jax.random.split(jax.random.PRNGKey(4), (4, 3)))
    assert float(jnp.abs(other[2] - got[2]).max() / jnp.abs(got[2]).max()) > 1e-2
