"""Compiles as the program's own counter (telemetry/compile_log.py): the active
`Telemetry` counts every backend compile of the process, and each is one event on the
sink with the function's name, whether the cache answered, and the step in flight."""

import json

import jax
import jax.numpy as jnp
import pytest

from modalities_tpu.telemetry import Telemetry, get_active_telemetry, set_active_telemetry
from modalities_tpu.telemetry.compile_log import BACKEND_COMPILE, CACHE_HIT, CompileLog


def compile_something_new(salt: float):
    return jax.jit(lambda x: jnp.sin(x) * salt + salt)(jnp.ones((3,), jnp.float32)).block_until_ready()


def events_of(telemetry: Telemetry) -> list[dict]:
    return [e for e in map(json.loads, telemetry.sink_path.read_text().splitlines()) if e.get("event") == "compile"]


def test_log_records_function_seconds_and_cache_hit_and_stops_when_closed():
    log = CompileLog()
    try:
        jax.monitoring.record_event(CACHE_HIT)
        jax.monitoring.record_event_duration_secs(BACKEND_COMPILE, 0.25, fun_name="prefill")
        jax.monitoring.record_event_duration_secs(BACKEND_COMPILE, 2.0, fun_name="decode_step")
        jax.monitoring.record_event_duration_secs("/jax/something/else", 9.0)
        assert log.compiles == [("prefill", 0.25, True), ("decode_step", 2.0, False)]
        assert log.summary("prefill", "decode") == {
            "prefill": {"count": 1, "cache_hits": 1, "first_s": 0.25, "total_s": 0.25},
            "decode": {"count": 1, "cache_hits": 0, "first_s": 2.0, "total_s": 2.0},
            "other": {"count": 0, "cache_hits": 0, "first_s": None, "total_s": 0.0},
        }
    finally:
        log.close()
    log.close()  # idempotent
    jax.monitoring.record_event_duration_secs(BACKEND_COMPILE, 1.0, fun_name="late")
    assert len(log.compiles) == 2


def test_active_telemetry_counts_compiles_and_names_the_step_in_flight(tmp_path):
    telemetry = Telemetry(output_folder_path=tmp_path, watchdog_deadline_s=0)
    previous = set_active_telemetry(telemetry)
    try:
        telemetry.arm_watchdog(7)
        compile_something_new(0.731)
        telemetry.beat_watchdog(7)  # step 7 is done: the next compile belongs to step 8
        jax.monitoring.record_event(CACHE_HIT)
        jax.monitoring.record_event_duration_secs(BACKEND_COMPILE, 0.5, fun_name="train_step")
    finally:
        set_active_telemetry(previous)
    jax.monitoring.record_event_duration_secs(BACKEND_COMPILE, 3.0, fun_name="after_deactivation")
    compiles = telemetry.metrics.counter("compile_total", "")
    seconds = telemetry.metrics.counter("compile_seconds_total", "")
    assert compiles.value(cache_hit="true") == 1 and compiles.value(cache_hit="false") >= 1
    assert seconds.value(cache_hit="true") == pytest.approx(0.5) and seconds.value(cache_hit="false") > 0
    events = events_of(telemetry)
    assert {e["step"] for e in events[:-1]} == {7} and any("lambda" in e["function"] for e in events[:-1])
    assert events[-1] == {**events[-1], "function": "train_step", "cache_hit": True, "step": 8, "seconds": 0.5}
    assert "after_deactivation" not in {e["function"] for e in events}
    assert get_active_telemetry() is previous


def test_a_disabled_telemetry_listens_to_nothing_and_is_forwarded_nothing():
    listeners = getattr(jax.monitoring, "get_event_duration_listeners", None)
    before = len(listeners()) if listeners is not None else None
    quiet = Telemetry(enabled=False)
    previous = set_active_telemetry(quiet)
    try:
        jax.monitoring.record_event_duration_secs(BACKEND_COMPILE, 0.5, fun_name="unheard")
    finally:
        set_active_telemetry(previous)
    assert quiet.metrics.counter("compile_total", "").value(cache_hit="false") == 0
    if before is not None:  # the process's record listens once, whoever is active
        assert len(listeners()) == before


def test_the_process_record_holds_a_compile_made_before_any_instance(tmp_path):
    """`PROCESS_COMPILES` listens from the package's import on: a compile made with no
    `Telemetry` anywhere is there with its stamp on `time.perf_counter()`, and an instance
    made later is forwarded the ones that follow, not that one."""
    import time

    from modalities_tpu.telemetry.compile_log import PROCESS_COMPILES
    from modalities_tpu.telemetry.spans import PROCESS_LOG

    assert get_active_telemetry().enabled is False
    t0 = time.perf_counter()
    compile_something_new(0.977)
    jax.monitoring.record_event(CACHE_HIT)
    jax.monitoring.record_event_duration_secs(BACKEND_COMPILE, 0.25, fun_name="early_step")
    t1 = time.perf_counter()
    early = [c for c in PROCESS_COMPILES if t0 <= c.at <= t1]
    assert early[-1] == (early[-1].at, "early_step", 0.25, True) and early[-1].at >= PROCESS_LOG.origin
    assert any("lambda" in c.function and not c.cache_hit and c.seconds > 0 for c in early[:-1])
    telemetry = Telemetry(output_folder_path=tmp_path, watchdog_deadline_s=0)
    previous = set_active_telemetry(telemetry)
    try:
        jax.monitoring.record_event_duration_secs(BACKEND_COMPILE, 1.5, fun_name="later_step")
    finally:
        set_active_telemetry(previous)
    assert [e["function"] for e in events_of(telemetry)] == ["later_step"]
    assert PROCESS_COMPILES[-1][1:] == ("later_step", 1.5, False)
    assert 0 < events_of(telemetry)[0]["end_s"] == pytest.approx(PROCESS_COMPILES[-1].at - PROCESS_LOG.origin, abs=0.05)


def test_flash_tile_plan_is_one_event_per_traced_shape_and_none_per_step(tmp_path, kernels_interpreted, tune_table):
    """The attention dispatch says once, while tracing, which tiles its kernels will
    compute (`flash_tile_plan`, beside `compile`); running the step says nothing more."""
    import modalities_tpu.ops.attention as attention
    import modalities_tpu.ops.pallas.flash_attention as flash

    tune_table({"flash_attention|*|*": {"block_q": 16, "block_k": 16}})
    telemetry = Telemetry(output_folder_path=tmp_path, watchdog_deadline_s=0)
    previous = set_active_telemetry(telemetry)
    try:
        two_layers = jax.jit(lambda x: attention.flash_attention_or_fallback(
            attention.flash_attention_or_fallback(x, x, x), x, x))  # one shape traced twice
        for _ in range(3):  # three steps of one executable
            two_layers(jnp.ones((1, 48, 2, 8), jnp.float32)).block_until_ready()
        jax.jit(lambda x: attention.flash_attention_or_fallback(x, x, x, causal=False))(
            jnp.ones((1, 32, 2, 8), jnp.float32)).block_until_ready()
    finally:
        set_active_telemetry(previous)
    plans = [e for e in map(json.loads, telemetry.sink_path.read_text().splitlines()) if e.get("name") == "flash_tile_plan"]
    assert [{k: v for k, v in e.items() if k not in ("event", "name", "rank")} for e in plans] == [
        {"seq_q": 48, "seq_k": 48, "block_q": 16, "block_k": 16, "causal": True, "head_dim": 8, "head_dim_v": 8,
         "computed": 6, "interior": 3, "diagonal": 3, "skipped_steps": 0, **flash.backward_plan(48, 16, 16, 8, 8, jnp.float32)},
        {"seq_q": 32, "seq_k": 32, "block_q": 16, "block_k": 16, "causal": False, "head_dim": 8, "head_dim_v": 8,
         "computed": 4, "interior": 4, "diagonal": 0, "skipped_steps": 0, **flash.backward_plan(32, 16, 16, 8, 8, jnp.float32)},
    ]


def test_flash_tile_plan_says_which_backward_a_differentiated_call_runs(tmp_path, monkeypatch, kernels_interpreted, tune_table):
    """PR 31: the event names the backward the shape rule picks (`backward`: "fused", or "two_kernels" where a q
    head's dq row does not fit the budget), the fused kernel's own blocks, the bytes of dq that stay in VMEM and the fused call's counted need; a
    differentiated call of two layers of one shape, run three times, says it once, and the program holds that kernel."""
    import modalities_tpu.ops.attention as attention
    import modalities_tpu.ops.pallas.flash_attention as flash

    tune_table({"flash_attention|*|*": {"block_q": 16, "block_k": 16}})

    def loss(x):
        return attention.flash_attention_or_fallback(attention.flash_attention_or_fallback(x, x, x), x, x).sum()

    def plans_of_a_traced_step(folder):
        telemetry = Telemetry(output_folder_path=folder, watchdog_deadline_s=0)
        previous = set_active_telemetry(telemetry)
        try:
            step = jax.jit(jax.grad(loss))
            for _ in range(3):
                step(jnp.ones((1, 48, 2, 8), jnp.bfloat16)).block_until_ready()
            kernels = str(jax.make_jaxpr(jax.grad(loss))(jnp.ones((1, 48, 2, 8), jnp.bfloat16)))
        finally:
            set_active_telemetry(previous)
        events = [e for e in map(json.loads, telemetry.sink_path.read_text().splitlines()) if e.get("name") == "flash_tile_plan"]
        return [{k: e[k] for k in ("backward", "backward_block_q", "backward_block_k", "dq_resident_bytes", "backward_vmem_bytes")}
                for e in events], kernels

    resident = 48 * 128 * (4 + 2 * 2)  # a float32 row of 8 lanes padded to 128 and its bfloat16 block twice
    need = flash.fused_backward_vmem_bytes(48, 16, 16, 8, 8, 2)
    plans, kernels = plans_of_a_traced_step(tmp_path / "fits")
    sized = {"backward_block_q": 16, "backward_block_k": 16, "dq_resident_bytes": resident, "backward_vmem_bytes": need}
    assert plans == [{"backward": "fused", **sized}]
    assert kernels.count("name=flash_attention_bwd\n") == 2 and "flash_attention_bwd_d" not in kernels
    monkeypatch.setattr(flash, "FUSED_BWD_VMEM_BUDGET", need - 1)
    plans, kernels = plans_of_a_traced_step(tmp_path / "does_not_fit")
    assert plans == [{"backward": "two_kernels", **sized}]
    assert kernels.count("name=flash_attention_bwd_dq") == 2 == kernels.count("name=flash_attention_bwd_dkv")


@pytest.mark.parametrize("kernel", [False, True], ids=["gathers_off_the_chip", "kernel_interpreted"])
def test_moe_dispatch_plan_is_one_event_per_traced_shape_and_none_per_step(tmp_path, kernel):
    """The expert layer says once, while tracing, what its dispatch is sized for (`moe_dispatch_plan`): the tokens,
    the router's width and a token's choices, the experts held and from where, the rows its tables hold (every pair
    on held experts, each group's last tile padded), the tile, and since PR 39 the form of the sum by token
    (`combine`: `slabs` where `combine_plan` takes the kernel and kernels run, which `kernels` then names; `gathers` for
    the plain form, as off the chip and for fewer tokens than a block), the kernel's block and the (block, expert)
    pairs it can have in use at most; two expert layers of one shape and three steps of one executable say it once."""
    from tests.models.test_moe_mla import build

    import contextlib

    from modalities_tpu.ops import tiers
    from modalities_tpu.ops.expert_dispatch import TILE, rows_for

    model = build()
    telemetry = Telemetry(output_folder_path=tmp_path, watchdog_deadline_s=0)
    previous = set_active_telemetry(telemetry)
    try:
        with tiers.interpreted_kernels() if kernel else contextlib.nullcontext():
            params = jax.jit(model.init_params)(jax.random.PRNGKey(0))  # the initializer's dummy of 8 tokens is a shape too
            apply = jax.jit(lambda p, t: model.apply(p, {"input_ids": t})["logits"])
            for _ in range(3):
                apply(params, jnp.zeros((2, 256), jnp.int32)).block_until_ready()
    finally:
        set_active_telemetry(previous)
    plans = [e for e in map(json.loads, telemetry.sink_path.read_text().splitlines()) if e.get("name") == "moe_dispatch_plan"]
    # with the kernel: 512 tokens of width 128 are two blocks of whole lane tiles; the dummy's 8 tokens are under one block
    assert [{k: v for k, v in e.items() if k not in ("event", "name", "rank")} for e in plans] == [
        {"tokens": tokens, "router_width": 8, "choices": 3, "experts_held": 4, "expert_offset": 2, "tile": TILE,
         "rows": rows_for(3 * tokens, 4, TILE), "kernels": ["moe_combine"] if slabs else [], "combine": "slabs" if slabs else "gathers",
         "combine_block": block, "combine_blocks_at_most": 4 * blocks}
        for tokens, slabs, block, blocks in ((8, False, 16, 1), (512, kernel, 256, 2))]


@pytest.mark.parametrize("kernels", [False, True], ids=["plain_form", "kernels_interpreted"])
def test_ssm_scan_plan_is_one_event_per_traced_shape_and_none_per_step(tmp_path, monkeypatch, kernels):
    """The state-space mixer says once, while tracing, how its scan walks the sequence
    (`ssm_scan_plan`): the chunks, the state carried, what the backward pass holds of a
    chunk, and whether the Pallas kernels were traced (as on a TPU) with their block,
    grid and the VMEM their backward keeps a chunk's states in; two layers of one shape
    and three steps of one executable say it once."""
    from tests.models.test_hybrid_ssm import HYBRID

    from modalities_tpu.models.gpt2.gpt2_model import GPT2LLM

    monkeypatch.setattr("modalities_tpu.ops.selective_scan.CHUNK", 24)
    monkeypatch.setattr("modalities_tpu.ops.selective_scan.uses_kernels", lambda interpret=False: kernels)
    model = GPT2LLM(**HYBRID)
    telemetry = Telemetry(output_folder_path=tmp_path, watchdog_deadline_s=0)
    previous = set_active_telemetry(telemetry)
    try:
        params = jax.jit(model.init_params)(jax.random.PRNGKey(0))  # the initializer's dummy of 8 tokens is a shape too
        apply = jax.jit(lambda p, t: model.apply(p, {"input_ids": t})["logits"])
        for _ in range(3):
            apply(params, jnp.zeros((2, 64), jnp.int32)).block_until_ready()
        apply(params, jnp.zeros((1, 40), jnp.int32)).block_until_ready()
    finally:
        set_active_telemetry(previous)
    plans = [e for e in map(json.loads, telemetry.sink_path.read_text().splitlines()) if e.get("name") == "ssm_scan_plan"]
    assert [(e["batch"], e["seq"], e["chunk"], e["chunks"]) for e in plans] == [(1, 8, 8, 1), (2, 64, 24, 3), (1, 40, 24, 2)]
    assert plans[1] == {**plans[1], "d_inner": 256, "d_state": 8, "state_bytes_carried": 4 * 2 * 256 * 8,
                        "boundary_state_bytes": 3 * 4 * 2 * 256 * 8, "backward_bytes_per_chunk": 24 * 4 * 2 * 256 * 8}
    of_kernels = [(e["kernel"], e["block_d"], e["grid_steps"], e["vmem_state_bytes"]) for e in plans]
    if kernels:  # one block of 256 channels; a grid step a sequence and chunk
        assert of_kernels == [(True, 256, 1, 4 * 8 * 8 * 256), (True, 256, 6, 4 * 24 * 8 * 256), (True, 256, 2, 4 * 24 * 8 * 256)]
    else:
        assert of_kernels == [(False, 0, 0, 0)] * 3


def test_fused_ce_plan_is_one_event_per_traced_shape_and_none_per_step(tmp_path):
    """The fused cross entropy says once, while tracing, what a call does (`fused_ce_plan`):
    the shape as the kernels hold it, the blocks, each kernel's grid steps, and whether the
    forward carries d_hidden, which only a differentiated call does: it then computes the
    logits twice (8 rows x n_embd x vocab of matmul for the 6 required) and the lean call
    once; three steps of one executable say nothing more."""
    from modalities_tpu.ops.cross_entropy import fused_ce_sum_and_count

    hidden, head = jnp.ones((2, 20, 32), jnp.bfloat16), jnp.ones((300, 32), jnp.bfloat16)
    labels = jnp.zeros((2, 20), jnp.int32)
    loss = lambda hidden, head: fused_ce_sum_and_count(hidden, head, labels, interpret=True)[0]  # noqa: E731
    telemetry = Telemetry(output_folder_path=tmp_path, watchdog_deadline_s=0)
    previous = set_active_telemetry(telemetry)
    try:
        step = jax.jit(jax.grad(lambda hidden, head: loss(hidden, head) + loss(2 * hidden, head), argnums=(0, 1)))  # one shape traced twice
        for _ in range(3):  # three steps of one executable
            jax.block_until_ready(step(hidden, head))
        jax.block_until_ready(jax.jit(loss)(hidden, head))  # the evaluator: nobody differentiates it
    finally:
        set_active_telemetry(previous)
    plans = [e for e in map(json.loads, telemetry.sink_path.read_text().splitlines()) if e.get("name") == "fused_ce_plan"]
    shape = {"rows": 64, "vocab": 300, "vocab_padded": 512, "n_embd": 32, "block_rows": 64, "block_vocab": 512, "grid_steps_forward": 1}
    assert [{k: v for k, v in e.items() if k not in ("event", "name", "rank", "forward_vmem_bytes")} for e in plans] == [
        {**shape, "dh_in_forward": True, "grid_steps_bwd_dw": 1, "nev_done": 8, "nev_required": 6},
        {**shape, "dh_in_forward": False, "grid_steps_bwd_dw": 0, "nev_done": 2, "nev_required": 2},
    ]
    # carrying d_hidden costs the forward an fp32 accumulator and a double-buffered fp32 output block
    assert plans[0]["forward_vmem_bytes"] - plans[1]["forward_vmem_bytes"] == 12 * 64 * 32
