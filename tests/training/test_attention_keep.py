"""Under `full` remat a block keeps the flash kernel's o and lse beside its input (PR 41): the recomputed forward has no
use for `flash_attention*_fwd` and the backward holds ONE call of it where it held two. Since PR 48 a block whose mixer is the
gated delta rule keeps the rule's o and group states one rung above them (`tests/ops/test_gated_delta_rule.py` counts the rule's forwards). On a CPU the kernels run
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
from tests.ops.test_gated_delta_rule import programs

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
    for eqn in programs(jaxpr, []):
        if eqn.primitive.name == "pallas_call":
            calls[eqn.params["name"]] = calls.get(eqn.params["name"], 0) + 1
        if eqn.primitive.name == "name":
            named[eqn.params["name"]] = eqn.outvars[0].aval
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
    # PR 48: one attention layer at 16 heads of 256 and three layers of the gated delta rule, each o `[16384, 32, 128]` bfloat16 and 8 group states of
    # 32 heads of 128 x 128 float32; 14.38 GiB compiled with both kept, 13.55 with the flash kernel's two or with nothing
    "train-qwen3next-80b-16k": ({"blocks": 4, "block_input_bytes": 64 * MIB, "calls": [
        {"kind": "attn", "layers": 1, "o_bytes": 128 * MIB, "lse_bytes": MIB, "backward_bytes": 840957952}],
        "rule": {"layers": 3, "o_bytes": 128 * MIB, "states_bytes": 16 * MIB}}, 6195794696, 4113281280, "fits", 14.38),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_plan_over_the_cells(cell):
    calls, state_bytes, gradient_bytes, verdict, compiled_gib = CELLS[cell]
    plan = attention_keep_plan(calls, state_bytes=state_bytes, gradient_bytes=gradient_bytes, bytes_limit=V5E)
    assert plan["verdict"] == verdict and plan["keep"] == (verdict == "fits") and plan["bytes_limit"] == V5E
    if calls is None:
        assert plan["layers"] == plan["kept_bytes"] == plan["rule_layers"] == plan["rule_kept_bytes"] == 0 and plan["kept"] == () and not plan["keep_rule"]
        return
    rule = calls.get("rule")
    assert plan["layers"] == sum(call["layers"] for call in calls["calls"])
    assert plan["kept_bytes"] == sum(call["layers"] * (call["o_bytes"] + call["lse_bytes"]) for call in calls["calls"])
    assert plan["kept"] == (("flash", "rule") if rule else ("flash",)) and plan["rung"] == 0 and plan["keep_rule"] == bool(rule)
    assert (plan["rule_layers"], plan["rule_kept_bytes"]) == ((3, 452984832) if rule else (0, 0))
    # the count never reads under the compiler (a `fits` the preflight would overturn costs a second lowering), nor a GiB over it; but of a rule
    # layer it holds the kept bytes alone (no third fitted constant), and reads under the compiler there by what a group's working set is
    assert compiled_gib - (0.8 if rule else 0.01) <= plan["counted_bytes"] / GIB <= compiled_gib + 1.0
    assert (plan["counted_bytes"] > V5E) == (compiled_gib * GIB > V5E)  # and says of each cell what the compiler says
    # no limit (a CPU): keep; the preflight's verdict on a step that kept: one rung down
    free = attention_keep_plan(calls, state_bytes=state_bytes, gradient_bytes=gradient_bytes, bytes_limit=None)
    assert free["keep"] and free["verdict"] == "fits" and free["kept_bytes"] == plan["kept_bytes"] and free["kept"] == plan["kept"]
    dropped = attention_keep_plan(calls, state_bytes=state_bytes, gradient_bytes=gradient_bytes, bytes_limit=None, first_rung=len(plan["kept"]))
    assert not dropped["keep"] and not dropped["keep_rule"] and dropped["kept"] == () and dropped["verdict"] == "fell_back_in_preflight"


def test_the_seventh_cells_numbers():
    """What the issue asks the chip's log to read: one flash call of 135,266,304 bytes, three rule layers of 452,984,832 in all."""
    calls, state_bytes, gradient_bytes, _, _ = CELLS["train-qwen3next-80b-16k"]
    plan = attention_keep_plan(calls, state_bytes=state_bytes, gradient_bytes=gradient_bytes, bytes_limit=16909336064)  # the chip's own `bytes_limit`
    assert (plan["layers"], plan["kept_bytes"], plan["rule_layers"], plan["rule_kept_bytes"]) == (1, 135266304, 3, 452984832)
    assert plan["verdict"] == "fits" and plan["counted_bytes"] == 14592508936
    without = attention_keep_plan({k: v for k, v in calls.items() if k != "rule"}, state_bytes=state_bytes, gradient_bytes=gradient_bytes, bytes_limit=V5E)
    assert plan["counted_bytes"] - without["counted_bytes"] == 452984832  # the kept bytes and nothing else: the rule's backward holds a group's working set either way


# a limit a byte under a rung's count, by what the rungs below it count: (limit below the count of everything, of the flash kernel's two, of nothing)
LADDER = {"all_fits": (None, ("flash", "rule"), "fits"), "between_the_two_rungs": (452984832, ("flash",), "over_count"),
          "under_both": (452984832 + 135266304, (), "over_count")}


@pytest.mark.parametrize("case", sorted(LADDER))
def test_the_ladder_flash_and_rule_then_flash_alone_then_nothing(case):
    calls, state_bytes, gradient_bytes, _, _ = CELLS["train-qwen3next-80b-16k"]
    below, kept, verdict = LADDER[case]
    everything = attention_keep_plan(calls, state_bytes=state_bytes, gradient_bytes=gradient_bytes, bytes_limit=None)["counted_bytes"]
    limit = V5E if below is None else everything - below + (below // 2 if case == "between_the_two_rungs" else -1)
    plan = attention_keep_plan(calls, state_bytes=state_bytes, gradient_bytes=gradient_bytes, bytes_limit=limit)
    assert plan["kept"] == kept and plan["rung"] == 2 - len(kept) and plan["verdict"] == verdict
    assert plan["keep"] == ("flash" in kept) and plan["keep_rule"] == ("rule" in kept) and plan["counted_bytes"] == everything
    # the preflight's steps: from rung 1 the rule is not kept whatever the count, from rung 2 nothing is
    for first_rung, most in ((1, ("flash",)), (2, ())):
        stepped = attention_keep_plan(calls, state_bytes=state_bytes, gradient_bytes=gradient_bytes, bytes_limit=limit, first_rung=first_rung)
        assert stepped["kept"] == kept[:len(most)] and stepped["verdict"] == "fell_back_in_preflight" and stepped["rung"] >= first_rung


def test_a_stack_of_rule_layers_alone_keeps_the_rules_two_or_nothing():
    calls = {"blocks": 3, "block_input_bytes": 64 * MIB, "calls": [], "rule": {"layers": 3, "o_bytes": 128 * MIB, "states_bytes": 16 * MIB}}
    plan = attention_keep_plan(calls, state_bytes=GIB, gradient_bytes=GIB, bytes_limit=V5E)
    assert plan["kept"] == ("rule",) and plan["keep_rule"] and not plan["keep"] and plan["verdict"] == "fits" and plan["layers"] == 0
    assert attention_keep_plan(calls, state_bytes=GIB, gradient_bytes=GIB, bytes_limit=4 * GIB)["kept"] == ()


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
    assert not attention_keep_plan(padded, **sizes)["keep"] and attention_keep_plan(padded, **sizes)["rung"] == 1


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
    """What `Trainer._preflight_memscope` reads of a build: the reports its lowerings would give, in turn, each lowering planning
    from the rung the preflight left it (`names`: what the top rung keeps)."""

    def __init__(self, *peaks, names=("flash",)):
        self.reports = [{"predicted_peak_bytes": peak, "buckets": {}, "context": {}} for peak in peaks]
        self.lower_train_step = object()
        self.kept_attention, self.names, self.lowered = KeptAttention(), names, []

    def memscope_report(self, batch):
        rung = min(self.kept_attention.first_rung, len(self.names))
        kept = self.names[:len(self.names) - rung]
        self.kept_attention.plan = {"kept": kept, "rung": rung, "keep": "flash" in kept, "keep_rule": "rule" in kept, "layers": 10, "kept_bytes": 340787200,
                                    "rule_layers": 3, "rule_kept_bytes": 452984832, "verdict": "fell_back_in_preflight" if rung else "fits"}
        self.lowered.append(kept)
        return self.reports.pop(0)


def test_the_preflight_builds_the_step_without_keeping_where_the_keeping_step_is_over_budget(monkeypatch):
    monkeypatch.setattr("modalities_tpu.trainer.min_bytes_limit", lambda: V5E)
    monkeypatch.setattr("modalities_tpu.telemetry.memscope.min_bytes_limit", lambda: V5E)
    fits = _Steps(V5E - 1)
    assert Trainer._preflight_memscope(fits, None)["predicted_peak_bytes"] == V5E - 1 and fits.kept_attention.first_rung == 0
    over = _Steps(V5E + 1, V5E - 2**28)  # the keeping step is over, the step the model had before is not
    assert Trainer._preflight_memscope(over, None)["predicted_peak_bytes"] == V5E - 2**28
    assert over.kept_attention.first_rung == 1 and not over.reports and over.lowered == [("flash",), ()]
    with pytest.raises(FitsCheckFailure):  # only the plain step's report can fail the check
        Trainer._preflight_memscope(_Steps(V5E + 2**28, V5E + 1), None)
    plain = _Steps(V5E + 1, names=())  # a step that keeps nothing has no second form
    with pytest.raises(FitsCheckFailure):
        Trainer._preflight_memscope(plain, None)
    assert plain.kept_attention.first_rung == 0


# the peaks the lowerings report, in turn -> what each lowering kept, and whether the last one passes the check
STEPS_DOWN = {"both_fit": ((V5E - 1,), [("flash", "rule")], True), "the_rule_does_not": ((V5E + 1, V5E - 1), [("flash", "rule"), ("flash",)], True),
              "neither_does": ((V5E + 2, V5E + 1, V5E - 1), [("flash", "rule"), ("flash",), ()], True),
              "nor_the_plain_step": ((V5E + 3, V5E + 2, V5E + 1), [("flash", "rule"), ("flash",), ()], False)}


@pytest.mark.parametrize("case", sorted(STEPS_DOWN))
def test_the_preflight_steps_down_one_rung_at_a_time_and_only_the_last_step_can_fail(monkeypatch, case):
    monkeypatch.setattr("modalities_tpu.trainer.min_bytes_limit", lambda: V5E)
    monkeypatch.setattr("modalities_tpu.telemetry.memscope.min_bytes_limit", lambda: V5E)
    peaks, lowered, passes = STEPS_DOWN[case]
    steps = _Steps(*peaks, names=("flash", "rule"))
    if passes:
        assert Trainer._preflight_memscope(steps, None)["predicted_peak_bytes"] == peaks[-1]
    else:
        with pytest.raises(FitsCheckFailure):
            Trainer._preflight_memscope(steps, None)
    assert steps.lowered == lowered and not steps.reports and steps.kept_attention.first_rung == len(lowered) - 1


def test_dropping_has_the_next_trace_plan_without_keeping():
    """`KeptAttention.drop` forgets the build's traces: the next call traces the step again, and plans it from one rung down."""
    kept, traced = KeptAttention(), []

    @jax.jit
    def step(x):
        traced.append(kept.first_rung)
        kept.plan = {"rung": kept.first_rung}
        return x + 1

    kept.jitted.append(step)
    step(1.0), step(2.0)
    kept.drop()
    step(3.0)
    kept.drop()
    step(4.0)
    assert traced == [0, 1, 2]


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
        assert gauge("train_remat_kept_rule_layers") == 0 and gauge("train_remat_kept_rule_bytes") == 0 and not model.config_spec.remat_keep_rule  # no rule layer
        assert gauge("train_remat_keep_verdict", verdict="fits") == 1
        fns.kept_attention.drop()
        fns.lower_train_step(batch)
        assert fns.kept_attention.plan["verdict"] == "fell_back_in_preflight" and not model.config_spec.remat_keep_flash
        assert gauge("train_remat_kept_attention_layers") == 0 and gauge("train_remat_kept_attention_bytes") == 0
        assert gauge("train_remat_keep_verdict", verdict="fits") == 0 and gauge("train_remat_keep_verdict", verdict="fell_back_in_preflight") == 1
    finally:
        set_active_telemetry(previous)


def test_a_step_over_rule_layers_keeps_the_rules_two_and_steps_down_a_rung_at_a_time(kernels_interpreted, tmp_path):
    """The toy of `tests/models/test_gdn_moe.py` (one layer of the gated delta rule, one of attention) lowered, not compiled: the
    plan's answer on the spec, in the five gauges and in both events, at each of the ladder's three rungs."""
    import json

    import numpy as np

    from modalities_tpu.telemetry import Telemetry, set_active_telemetry
    from tests.models.test_gdn_moe import SEQ as ROW, build
    from tests.training.test_train_step import _batch, _builder

    telemetry = Telemetry(output_folder_path=tmp_path)
    previous = set_active_telemetry(telemetry)
    try:
        model = build(attention_implementation="dao_flash").with_spec_updates(remat_variant="full")
        fns = _builder(model, None).build(seed=0, materialize=False)
        batch = _batch(np.random.default_rng(0), 1, 2, ROW, vocab=512)
        gauge = lambda name: telemetry.metrics.gauge(name).value()  # noqa: E731
        rule_bytes = 2 * ROW * 4 * 16 * 2 + 2 * 4 * 16 * 16 * 4  # o in bfloat16, one group's state a value head in float32
        for kept in (("flash", "rule"), ("flash",), ()):
            fns.lower_train_step(batch)
            plan, spec = fns.kept_attention.plan, model.config_spec
            assert plan["kept"] == kept and (spec.remat_keep_flash, spec.remat_keep_rule) == ("flash" in kept, "rule" in kept)
            assert (plan["layers"], plan["rule_layers"], plan["rule_kept_bytes"]) == (1, 1, rule_bytes)
            assert plan["verdict"] == ("fits" if len(kept) == 2 else "fell_back_in_preflight")
            assert gauge("train_remat_kept_rule_layers") == ("rule" in kept) and gauge("train_remat_kept_rule_bytes") == rule_bytes * ("rule" in kept)
            assert gauge("train_remat_kept_attention_layers") == ("flash" in kept) and gauge("train_remat_kept_attention_bytes") == plan["kept_bytes"] * ("flash" in kept)
            fns.kept_attention.drop()
        events = [json.loads(line) for line in telemetry.sink_path.read_text().splitlines() if line.strip()]
    finally:
        set_active_telemetry(previous)
    plans = [e for e in events if e.get("name") == "attention_keep_plan"]
    assert [tuple(e["kept"]) for e in plans] == [("flash", "rule"), ("flash",), ()] and [e["rung"] for e in plans] == [0, 1, 2]
    rules = [e for e in events if e.get("name") == "gdn_plan" and e["tokens"] == 2 * ROW]  # once a distinct payload: the keeping step's, then the steps' that do not keep it
    assert [e["forwards_a_step"] for e in rules] == [2, 3]
    assert "the block kept o and the group states" in rules[0]["backward"] and "runs the rule once more" in rules[1]["backward"]
