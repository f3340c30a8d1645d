"""benchmark/readers/collectives.py on a hand-made trace of two devices and a plan of two axes: every metric's value
worked out by hand, a straggler read by `device_idle_max_pct`, the unmatched rule, the fall-back without a plan, and the
join held to the module that ran. The arithmetic is the reader's; the chip gives the numbers (PERF.md section 5)."""

import pytest

from benchmark import xtrace
from benchmark.manifest import load_module
from tests.benchmark.toy import REPO

collectives = load_module(REPO, "readers", "collectives")
US = 1e-6


def event(name, start, end):
    return xtrace.Event(name, start * US, end * US)


def row(name, kind, axis, nbytes, times=1, done=None, steps=(), scope="blocks/block/mlp/W"):
    return {"name": name, "done": done, "steps": list(steps), "kind": kind, "axis": axis, "bytes": nbytes, "times": times, "scope": scope}


PLAN = {
    "module": "jit_train_step", "mesh_axes": {"dp_shard": 2, "tp": 2}, "est_seconds_a_run": 1e-5,
    "rows": [
        row("all-gather-start.9", "all-gather", "tp", 1000, times=3, done="all-gather-done.9", scope="jvp(GPT2Module)/layer_carry/blocks/block/attn/q_attn"),
        row("all-gather.5", "all-gather", "tp", 500, scope="jvp(GPT2Module)/layer_carry/blocks/block/mlp/W"),
        row("async-collective-start.2", "all-gather", "dp_shard", 2000, times=2, done="async-collective-done.2", steps=["fusion.7"],
            scope="transpose(jvp(GPT2Module))/layer_carry/blocks/block/mlp/V"),
        row("fusion.9", "reduce-scatter", "dp_shard", 4000, scope="transpose(jvp(GPT2Module))/layer_carry/blocks/block/mlp/W_2"),
        row("psum.3", "all-reduce", "dp_shard+tp", 4, scope="jvp(head_loss)/shard_map"),
    ],
}
PLAN["bytes_a_run"] = sum(r["bytes"] * r["times"] for r in PLAN["rows"])


def device(ordinal, done9, gather5, start2, step7, last, extra=()):
    """One device's 100 us: compute, the wait for an all-gather that was in flight beside it, a synchronous all-gather, an
    asynchronous one cut into a start, a compute fusion that carries its steps and a done, nothing for 4 us, a
    reduce-scatter in the chip's wrapper, a psum, compute from `last` on."""
    ops = [event("%fusion.1 = bf16[8] fusion(%p), kind=kLoop", 0, 25),
           event("%all-gather-done.9 = bf16[8] all-gather-done(%all-gather-start.9)", *done9),
           event("%all-gather.5 = bf16[8] all-gather(%x), replica_groups=[2,2]<=[4]", *gather5),
           event("%async-collective-start.2 = (bf16[4], bf16[8]) fusion(%w), kind=kCustom, calls=%fused_computation.2", *start2),
           event("%fusion.7 = bf16[8] fusion(%a, %b), kind=kOutput, calls=%async_collective_fusion.7", *step7),
           event("%async-collective-done.2 = bf16[8] fusion(%a, %b), kind=kCustom, calls=%fused_computation.3", 60, 66),
           event("%fusion.9 = bf16[4] fusion(%g), kind=kCustom, calls=%all-reduce-scatter.1", 70, 80),
           event("%psum.3 = f32[] all-reduce(%l), replica_groups={{0,1,2,3}}", 80, 82),
           event("%fusion.2 = bf16[8] fusion(%q), kind=kLoop", last, 100), *extra]
    return xtrace.DeviceTrace(ordinal, ops, [event("jit_train_step(1)", 0, 100)],
                              [event("%all-gather-start.9 = (bf16[4], bf16[8]) all-gather-start(%w)", 5, done9[1])])


def by_hand(extra=()):
    host = [xtrace.Event("metrics_fetch", 64 * US, 72 * US, 0), xtrace.Event("train_step", 88 * US, 101 * US, 0)]
    return xtrace.Trace([device(0, (25, 30), (30, 40), (40, 42), (42, 60), 82, extra),
                         device(1, (25, 35), (35, 45), (45, 47), (47, 60), 92)], host)


def test_every_metric_by_hand():
    """Device 0: tp under way 5-40 (the gather in flight from 5, waited for 25-30, then the synchronous one), exposed 25-40;
    dp_shard under way 40-66 and 70-80, exposed 40-42, 60-66 and 70-80 (the step fusion 42-60 is compute and hides the
    gather); the psum 80-82; idle 66-70. Device 1, the straggler: the wait is 25-35, so tp is under way 5-45 and exposed
    25-45, dp_shard under way 45-66 and 70-80, and its last compute waits until 92 for the host: busy 86 of 100."""
    found = collectives.reading(by_hand(), PLAN)
    assert found["window_s"] == pytest.approx(100 * US)
    zero, one = found["devices"]
    assert zero["groups"]["(all)"] == pytest.approx((73 * US, 35 * US)) and one["groups"]["(all)"] == pytest.approx((73 * US, 40 * US))
    assert zero["groups"]["tp"] == pytest.approx((35 * US, 15 * US)) and one["groups"]["tp"] == pytest.approx((40 * US, 20 * US))
    assert zero["groups"]["dp_shard"] == pytest.approx((36 * US, 18 * US)) and one["groups"]["dp_shard"] == pytest.approx((31 * US, 18 * US))
    assert zero["rows"]["async-collective-start.2"] == pytest.approx((26 * US, 8 * US))  # 40-66 in flight, 42-60 of it under the step fusion
    assert zero["rows"]["all-gather-start.9"] == pytest.approx((25 * US, 5 * US))  # the async line's 5-30 and the done's 25-30
    assert found["in_flight_pct"] == pytest.approx(73.0) and found["exposed_pct"] == pytest.approx(37.5)
    assert found["by_axis"]["tp"]["exposed_pct"] == pytest.approx(17.5) and found["by_axis"]["dp_shard"]["exposed_pct"] == pytest.approx(18.0)
    assert found["by_axis"]["dp_shard+tp"]["exposed_pct"] == pytest.approx(2.0)
    # the axes' rows never overlap here, so the parts add up to the whole; and exposed never passes under way
    assert sum(a["exposed_pct"] for a in found["by_axis"].values()) == pytest.approx(found["exposed_pct"])
    assert found["exposed_pct"] <= found["in_flight_pct"]
    assert found["gb_per_step"] == pytest.approx((3 * 1000 + 500 + 2 * 2000 + 4000 + 4) / 1e9)
    assert found["unmatched_share"] == 0.0 and found["by_axis_held"]


def test_the_straggler_is_read_by_the_maximum_not_the_mean():
    trace = by_hand()
    found = collectives.reading(trace, PLAN)
    assert found["idle_max_pct"] == pytest.approx(14.0)  # device 1: busy 0-66, 70-82, 92-100
    assert 100 * xtrace.idle_share(trace) == pytest.approx(9.0)  # what device_idle_pct.train reads: (4 + 14) / 2
    assert [gap for gap in found["devices"][1]["idle_gaps"]] == [pytest.approx((66 * US, 70 * US)), pytest.approx((82 * US, 92 * US))]
    table = collectives.describe(found, trace.host_spans)
    assert "[mesh] idle on device 0, ms by the host span open meanwhile: metrics_fetch 0.004" in table
    assert "train_step 0.010" in table.splitlines()[-1] and "metrics_fetch 0.004" in table.splitlines()[-1]
    assert "reduce-scatter" in table and "dp_shard+tp" in table and "transpose(jvp(GPT2Module))/layer_carry/blocks/block/mlp/W_2" in table


def test_metrics_through_read_and_the_specs_of_the_manifest():
    trace, observed = by_hand(), {}
    monkey = {"modalities_tpu.telemetry.collective_plan": type("M", (), {"PROCESS_PLANS": [{"module": "jit_other", "rows": [row("x", "all-reduce", "tp", 1)]}, PLAN]})}
    import sys

    kept = {name: sys.modules.get(name) for name in monkey}
    sys.modules.update(monkey)
    try:
        values = {name: collectives.read(load_spec(name), observed, trace, {}) for name in METRICS}
    finally:
        for name, module in kept.items():
            sys.modules.pop(name) if module is None else sys.modules.__setitem__(name, module)
    assert values == {"collective_exposed_pct": pytest.approx(37.5), "collective_exposed_dp_shard_pct": pytest.approx(18.0),
                      "collective_exposed_tp_pct": pytest.approx(17.5), "collective_in_flight_pct": pytest.approx(73.0),
                      "collective_gb_per_step": pytest.approx(1.1504e-5), "device_idle_max_pct": pytest.approx(14.0)}
    assert collectives.read(load_spec("collective_exposed_pct"), {}, None, {}) is None  # no trace: nothing to read


METRICS = ("collective_exposed_pct", "collective_exposed_dp_shard_pct", "collective_exposed_tp_pct", "collective_in_flight_pct",
           "collective_gb_per_step", "device_idle_max_pct")


def load_spec(name):
    import json

    return json.loads((REPO / "benchmark" / "metrics" / f"{name}.json").read_text())


def test_more_than_two_percent_unmatched_and_the_axes_are_missing_not_guessed():
    stranger = event("%all-reduce.77 = f32[8] all-reduce(%z), replica_groups={{0,2},{1,3}}", 66, 70)  # in no row of the plan
    found = collectives.reading(by_hand(extra=(stranger,)), PLAN)
    assert found["devices"][0]["unmatched_names"] == ["all-reduce.77"] and found["devices"][0]["unmatched_s"] == pytest.approx(4 * US)
    assert found["unmatched_share"] == pytest.approx(2.0 / (73 + 2.0)) and not found["by_axis_held"]
    observed = {"collectives": found}
    assert collectives.read({"key": "exposed_pct", "axis": "tp"}, observed, by_hand(), {}) is None
    assert collectives.read({"key": "exposed_pct"}, observed, by_hand(), {}) == pytest.approx(37.5)  # over the rows: still read
    assert "MISSING" in collectives.describe(found, [])
    # a row no event matches is counted and printed, and costs nothing: its seconds are none
    plan = {**PLAN, "rows": [*PLAN["rows"], row("all-to-all.4", "all-to-all", "tp", 8)]}
    found = collectives.reading(by_hand(), plan)
    assert found["devices"][0]["unmatched_rows"] == ["all-to-all.4"] and found["by_axis_held"]
    assert "1 row(s) of the plan with no event ['all-to-all.4']" in collectives.describe(found, [])


def test_without_a_plan_the_old_pattern_reads_the_totals_and_no_axis():
    """A program from before the record: the events `xtrace.COLLECTIVE` names, and the wrappers whose instruction text
    says what they call. The psum is missed (nothing in its name says all-reduce but its opcode, which the label drops)."""
    found = collectives.reading(by_hand(), None)
    zero = found["devices"][0]
    # under way 5-42 (the start's own 2 us: that the gather stays in flight until its done, only the plan's pairing says),
    # 60-66 and 70-80; the psum's 2 us are compute to it
    assert zero["groups"]["(all)"] == pytest.approx((53 * US, 33 * US))
    assert found["by_axis"] == {} and found["gb_per_step"] is None and not found["by_axis_held"]
    assert collectives.read({"key": "exposed_pct", "axis": "tp"}, {"collectives": found}, by_hand(), {}) is None
    assert "no collective plan in this process" in collectives.describe(found, [])
    # what xtrace.exposed_collective_seconds, the function the benchmark had, sees of the same trace: the names
    # `async-collective-*` and the fusion round the reduce-scatter are not collectives to it (PERF.md section 7)
    # nor is the psum: it reads tp's 17.5 us of the 37.5 exposed
    assert xtrace.exposed_collective_seconds(by_hand()) == pytest.approx((15 + 20) / 2 * US)


def test_an_instruction_number_means_nothing_in_another_program():
    """`fusion.9` of a helper program that ran inside the window is not the plan's `fusion.9`."""
    trace = by_hand()
    for d in trace.devices:
        d.modules.append(event("jit_grad_norms(2)", 100, 104))
        d.ops.append(event("%fusion.9 = f32[4] fusion(%m), kind=kLoop", 100, 104))
    found = collectives.reading(trace, PLAN)
    assert found["window_s"] == pytest.approx(104 * US)
    assert found["devices"][0]["rows"]["fusion.9"] == pytest.approx((10 * US, 10 * US))
    assert found["exposed_pct"] == pytest.approx(100 * 37.5 / 104)
    assert collectives.plan_for(trace, [{"module": "jit_eval_step", "rows": [row("x", "all-reduce", "tp", 1)]}]) is None


def test_what_the_trace_nests_inside_a_wrapper_is_part_of_the_wrapper():
    """Where a device's trace shows a wrapper's inner collective as an event inside the wrapper's own, that event is neither
    compute that hides the collective nor a collective the plan does not know."""
    trace = by_hand()
    for d in trace.devices:
        d.ops.append(event("%all-reduce.92 = bf16[8] all-reduce(%input), replica_groups={{0,2},{1,3}}", 72, 78))
    found = collectives.reading(trace, PLAN)
    assert found["devices"][0]["rows"]["fusion.9"] == pytest.approx((10 * US, 10 * US))
    assert found["exposed_pct"] == pytest.approx(37.5) and found["unmatched_share"] == 0.0
