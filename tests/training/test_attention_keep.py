"""Under `full` remat a block keeps the flash kernel's o and lse beside its input (PR 41): the recomputed forward has no
use for `flash_attention*_fwd` and the backward holds ONE call of it where it held two. On a CPU the kernels run
interpreted (the fixture `kernels_interpreted`: `ops/tiers.interpreted_kernels`), through the dispatcher the model calls
(`ops/attention.flash_attention_or_fallback`), at toy size; what is
kept is decided by `training/activation_checkpointing.attention_keep_plan`, and `Trainer._preflight_memscope` is the net
under it."""


import jax
import jax.numpy as jnp
import pytest

from modalities_tpu.models.gpt2.gpt2_model import GPT2Block, _remat_block_cls
from modalities_tpu.ops.pallas import flash_attention as flash
from modalities_tpu.telemetry.memscope import FitsCheckFailure
from modalities_tpu.trainer import Trainer
from modalities_tpu.training.activation_checkpointing import attention_keep_plan
from modalities_tpu.training.train_step import KeptAttention
from tests.models.test_gpt2_model import tiny_gpt2

ROWS, SEQ = 2, 64
# what sits in the block's mixer seat, and the toy model's keys that put it there
KINDS = {
    "plain_causal": ("attn", {"n_head_kv": 4}),
    "grouped_heads": ("attn", {"n_head_kv": 2}),
    "window": ("swa", {"layer_types": ["sliding_attention", "full_attention"], "sliding_window": 24}),
    "dv_not_d": ("attn", {"n_head_kv": 4, "mla_config": {"kv_lora_rank": 64, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
                                                         "v_head_dim": 32, "rope_theta": 1e6}}),
    "compressed_heads": ("cca", {"layer_types": ["hybrid", "hybrid"], "cca_config": {"cca_time0": 2, "cca_time1": 2}, "head_dim": 32}),
}


def walked(jaxpr, calls: dict, named: dict):
    """Every `pallas_call` by its kernel's name, and every value a `checkpoint_name` marks, through all nested programs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            calls[name] = calls.get(name, 0) + 1
        if eqn.primitive.name == "name":
            named[eqn.params["name"]] = eqn.outvars[0].aval
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    walked(inner, calls, named)
    return calls, named


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_full_remat_block_that_keeps_o_and_lse_runs_the_forward_kernel_once(kernels_interpreted, kind):
    mixer, keys = KINDS[kind]
    x = jax.random.normal(jax.random.PRNGKey(1), (ROWS, SEQ, 128), jnp.bfloat16)
    spec = tiny_gpt2("manual", sequence_length=SEQ, **keys).config_spec  # the tree is the same under every tier, and this one traces fast
    leaves, tree = jax.tree.flatten(jax.eval_shape(GPT2Block(spec, mixer=mixer).init, jax.random.PRNGKey(0), x))
    params = jax.tree.unflatten(tree, [0.05 * jax.random.normal(jax.random.PRNGKey(i), leaf.shape, leaf.dtype) for i, leaf in enumerate(leaves)])
    model = tiny_gpt2("dao_flash", sequence_length=SEQ, **keys).with_spec_updates(remat_variant="full")
    results = {}
    for kept in (False, True):
        spec = model.with_spec_updates(remat_keep_flash=kept).config_spec
        block = _remat_block_cls(spec)(spec, False, mixer=mixer)

        def loss(params, x):
            out = block.apply(params, x)
            return (out[0] if isinstance(out, tuple) else out).astype(jnp.float32).var()

        traced = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(params, x)
        # the compressed mixer's block is counted and not run (its two programs compile for 9 s): its call is `grouped_heads`' kernel
        gradients = () if mixer == "cca" else traced.lower().compile()(params, x)
        results[kept] = (*walked(traced.jaxpr.jaxpr, {}, {}), gradients)
    forward = "flash_attention_window_fwd" if mixer == "swa" else "flash_attention_fwd"
    (calls, named, plain), (kept_calls, kept_named, kept) = results[False], results[True]
    assert calls[forward] == 2 and kept_calls[forward] == 1  # the recomputed forward's call is gone
    assert {name: n for name, n in calls.items() if name != forward} == {name: n for name, n in kept_calls.items() if name != forward}
    assert flash.KEPT_LSE not in named and flash.KEPT_OUT not in named
    heads = model.config_spec.n_head_q
    assert kept_named[flash.KEPT_LSE].shape == (ROWS, heads, 1, SEQ) and kept_named[flash.KEPT_LSE].dtype == jnp.float32  # as the kernel wrote it
    assert kept_named[flash.KEPT_OUT].shape == (ROWS, heads, SEQ, 32) and kept_named[flash.KEPT_OUT].dtype == jnp.bfloat16
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(kept)):
        assert a.dtype == b.dtype and float(jnp.abs(a.astype(jnp.float32)).max()) > 0 and bool((a == b).all())  # the kept arrays ARE the recomputed ones


def test_a_call_that_is_not_told_binds_what_it_bound(kernels_interpreted):
    """`kept=False` (every call outside a keeping block: the dense cell, the looped stack, ring attention's hops): the
    residuals are the kernel's own five arrays, lse as the kernel lays it out, and nothing carries a name."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, SEQ, 2, 32), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q: flash.pallas_flash_attention(q, q, q, interpret=True).astype(jnp.float32).sum()))(q)
    calls, named = walked(jaxpr.jaxpr, {}, {})
    assert calls == {"flash_attention_fwd": 1, "flash_attention_bwd": 1} and not named


GIB, MIB = 2**30, 2**20
V5E = int(15.75 * GIB)  # a v5e's `bytes_limit`
ATTENTION = {"o_bytes": 128 * MIB, "lse_bytes": 2 * MIB}  # 524,288 (row, head) pairs of 128 values: the third and the fifth cell's call
# each cell's step as one chip sees it (`benchmark/configs/*/train.yaml`): what `GPT2LLM.remat_flash_calls` and `TrainStepBuilder`
# hand the plan, the verdict at the v5e's limit, and what the compiler said of the keeping step (GiB, `memory_analysis()` for a
# described v5e: `scripts/attention_keep_sizes.py`, PERF.md section 6, PR 42); None: nothing to keep, the step is the parent's.
# `backward_bytes` holds lse and delta as the dense rows they are since PR 42 (2 x 2 MiB in the third cell, where it held 2 x 256)
CELLS = {
    "train-2p7b-4k": (None, 4258928648, 2839152640, "no_remat", None),  # no remat at depth 6
    "train-ouro-2p6b-4k": (None, 6142083092, 4094181380, "no_remat", None),  # the looped stack recomputes by hand
    "train-jamba2-3b-4k": ({"blocks": 14, "block_input_bytes": 20 * MIB, "calls": [
        {"kind": "attn", "layers": 1, "o_bytes": 20 * MIB, "lse_bytes": 327680, "backward_bytes": 128581632}]}, 9161563400, 6058680064, "fits", 14.84),
    "train-kanana2-30b-8k": ({"blocks": 9, "block_input_bytes": 64 * MIB, "calls": [
        {"kind": "attn", "layers": 9, **ATTENTION, "backward_bytes": 1346371584}]}, 6148073480, 4090148864, "fits", 15.31),
    "train-mellum2-12b-16k": ({"blocks": 12, "block_input_bytes": 72 * MIB, "calls": [
        {"kind": "attn", "layers": 3, **ATTENTION, "backward_bytes": 843055104},
        {"kind": "swa", "layers": 9, **ATTENTION, "backward_bytes": 843055104}]}, 5457742856, 3631186944, "fits", 13.92),
    "train-zaya1-8b-8k": ({"blocks": 10, "block_input_bytes": 64 * MIB, "calls": [
        {"kind": "cca", "layers": 10, "o_bytes": 32 * MIB, "lse_bytes": MIB // 2, "backward_bytes": 219152384}]}, 6859299056, 4545393400, "fits", 13.50),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_plan_over_the_six_cells(cell):
    calls, state_bytes, gradient_bytes, verdict, compiled_gib = CELLS[cell]
    plan = attention_keep_plan(calls, state_bytes=state_bytes, gradient_bytes=gradient_bytes, bytes_limit=V5E)
    assert plan["verdict"] == verdict and plan["keep"] == (verdict == "fits") and plan["bytes_limit"] == V5E
    if calls is None:
        assert plan["layers"] == plan["kept_bytes"] == 0
        return
    assert plan["layers"] == sum(call["layers"] for call in calls["calls"])
    assert plan["kept_bytes"] == sum(call["layers"] * (call["o_bytes"] + call["lse_bytes"]) for call in calls["calls"])
    # the count never reads under the compiler (a `fits` the preflight would overturn costs a second lowering), nor a GiB over it
    assert compiled_gib - 0.01 <= plan["counted_bytes"] / GIB <= compiled_gib + 1.0
    assert (plan["counted_bytes"] > V5E) == (compiled_gib * GIB > V5E)  # and says of each cell what the compiler says
    # no limit (a CPU): keep; the preflight's verdict on a step that kept: do not
    free = attention_keep_plan(calls, state_bytes=state_bytes, gradient_bytes=gradient_bytes, bytes_limit=None)
    assert free["keep"] and free["verdict"] == "fits" and free["kept_bytes"] == plan["kept_bytes"]
    dropped = attention_keep_plan(calls, state_bytes=state_bytes, gradient_bytes=gradient_bytes, bytes_limit=None, allowed=False)
    assert not dropped["keep"] and dropped["verdict"] == "fell_back_in_preflight"


def test_the_third_cell_keeps_because_its_statistics_are_numbers_now():
    """PR 42: the count holds lse and delta at their 4 bytes a number (`backward_bytes` ends in twice `lse_bytes`, no `* 128`);
    with the lane tile a number they took before (2 x 256 MiB here) the same count, doubled as a working set, is over the chip."""
    calls, state_bytes, gradient_bytes, _, _ = CELLS["train-kanana2-30b-8k"]
    (call,) = calls["calls"]
    operands = 2 * 8192 * 2 * (3 * 32 * 192 + 3 * 32 * 128 + 32 * (192 + 128))  # q, k, v, o, do, dq, dk, dv: bfloat16
    assert call["backward_bytes"] == operands + 2 * call["lse_bytes"]
    padded = {**calls, "calls": [{**call, "backward_bytes": operands + 2 * 128 * call["lse_bytes"]}]}
    sizes = dict(state_bytes=state_bytes, gradient_bytes=gradient_bytes, bytes_limit=V5E)
    assert attention_keep_plan(calls, **sizes)["verdict"] == "fits" and attention_keep_plan(padded, **sizes)["verdict"] == "over_count"
    assert not attention_keep_plan(padded, **sizes)["keep"]


def test_a_stack_with_no_attention_layer_under_remat_has_nothing_to_keep():
    plan = attention_keep_plan({"blocks": 14, "block_input_bytes": MIB, "calls": []}, state_bytes=1, gradient_bytes=1, bytes_limit=V5E)
    assert not plan["keep"] and plan["verdict"] == "no_remat" and plan["kept_bytes"] == 0


@pytest.mark.parametrize("keys, calls", [
    ({}, None),  # no remat
    ({"remat_variant": "selective_op"}, None),  # a user's save list stays as it is
    ({"remat_variant": "full", "pipeline_axis": "pp"}, None), ({"remat_variant": "full", "context_parallel_axis": "cp"}, None),
    ({"remat_variant": "full", "attention_impl": "manual"}, None),
    ({"remat_variant": "full"}, [{"kind": "attn", "layers": 2, "o_bytes": 2 * 4 * 64 * 32 * 2, "lse_bytes": 2 * 4 * 64 * 4,
                                  "backward_bytes": 2 * 64 * 2 * (6 * 4 * 32 + 2 * 2 * 32) + 2 * 2 * 4 * 64 * 4}]),  # lse and delta: 4 bytes a number
])
def test_the_model_names_the_calls_its_rematerialized_blocks_hold(monkeypatch, keys, calls):
    model = tiny_gpt2("dao_flash", sequence_length=SEQ).with_spec_updates(**keys)
    assert model.remat_flash_calls(ROWS, SEQ) is None  # off the TPU the blocks hold no kernel call, whatever the variant
    monkeypatch.setattr("modalities_tpu.ops.tiers.on_tpu", lambda: True)
    described = model.remat_flash_calls(ROWS, SEQ)
    assert described == (None if calls is None else {"blocks": 2, "block_input_bytes": ROWS * SEQ * 128 * 2, "calls": calls})


class _Steps:
    """What `Trainer._preflight_memscope` reads of a build: the reports its lowerings would give, in turn."""

    def __init__(self, *peaks, keep=True):
        self.reports = [{"predicted_peak_bytes": peak, "buckets": {}, "context": {}} for peak in peaks]
        self.lower_train_step = object()
        self.kept_attention = KeptAttention()
        self.kept_attention.plan = {"keep": keep, "layers": 10, "kept_bytes": 340787200, "verdict": "fits" if keep else "over_count"}

    def memscope_report(self, batch):
        return self.reports.pop(0)


def test_the_preflight_builds_the_step_without_keeping_where_the_keeping_step_is_over_budget(monkeypatch):
    monkeypatch.setattr("modalities_tpu.trainer.min_bytes_limit", lambda: V5E)
    monkeypatch.setattr("modalities_tpu.telemetry.memscope.min_bytes_limit", lambda: V5E)
    fits = _Steps(V5E - 1)
    assert Trainer._preflight_memscope(fits, None)["predicted_peak_bytes"] == V5E - 1 and fits.kept_attention.allowed
    over = _Steps(V5E + 1, V5E - 2**28)  # the keeping step is over, the step the model had before is not
    assert Trainer._preflight_memscope(over, None)["predicted_peak_bytes"] == V5E - 2**28
    assert not over.kept_attention.allowed and not over.reports
    with pytest.raises(FitsCheckFailure):  # only the plain step's report can fail the check
        Trainer._preflight_memscope(_Steps(V5E + 2**28, V5E + 1), None)
    plain = _Steps(V5E + 1, keep=False)  # a step that keeps nothing has no second form
    with pytest.raises(FitsCheckFailure):
        Trainer._preflight_memscope(plain, None)
    assert plain.kept_attention.allowed


def test_dropping_has_the_next_trace_plan_without_keeping():
    """`KeptAttention.drop` forgets the build's traces: the next call traces the step again, and plans it not allowed."""
    kept, traced = KeptAttention(), []

    @jax.jit
    def step(x):
        traced.append(kept.allowed)
        return x + 1

    kept.jitted.append(step)
    step(1.0), step(2.0)
    kept.drop()
    step(3.0)
    assert traced == [True, False]


def test_the_step_plans_while_it_is_traced_and_plans_again_once_dropped(kernels_interpreted):
    """Lowered, not compiled: the plan lands on the model's spec before the blocks are traced, in the build's seat and
    in the gauges; after `drop()` the same lowering traces a step that keeps nothing."""
    import numpy as np

    from modalities_tpu.telemetry import Telemetry, set_active_telemetry
    from tests.training.test_train_step import _batch, _builder

    telemetry = Telemetry()
    previous = set_active_telemetry(telemetry)
    try:
        model = tiny_gpt2("dao_flash", sequence_length=16).with_spec_updates(remat_variant="full")
        fns = _builder(model, None).build(seed=0, materialize=False)
        batch = _batch(np.random.default_rng(0), 1, 2, 16)
        assert fns.kept_attention.plan is None and not model.config_spec.remat_keep_flash
        fns.lower_train_step(batch)
        plan = fns.kept_attention.plan
        assert plan["verdict"] == "fits" and plan["layers"] == 2 and plan["bytes_limit"] is None and model.config_spec.remat_keep_flash
        gauge = lambda name, **labels: telemetry.metrics.gauge(name).value(**labels)  # noqa: E731
        assert gauge("train_remat_kept_attention_layers") == 2 and gauge("train_remat_kept_attention_bytes") == plan["kept_bytes"] > 0
        assert gauge("train_remat_keep_verdict", verdict="fits") == 1
        fns.kept_attention.drop()
        fns.lower_train_step(batch)
        assert fns.kept_attention.plan["verdict"] == "fell_back_in_preflight" and not model.config_spec.remat_keep_flash
        assert gauge("train_remat_kept_attention_layers") == 0 and gauge("train_remat_kept_attention_bytes") == 0
        assert gauge("train_remat_keep_verdict", verdict="fits") == 0 and gauge("train_remat_keep_verdict", verdict="fell_back_in_preflight") == 1
    finally:
        set_active_telemetry(previous)
