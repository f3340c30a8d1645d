"""Device time by scope (benchmark/xscope.py, benchmark/readers/scope_time.py) on a trace
and a table written by hand, where every number can be worked out on paper; on a profile
built from the xplane protocol buffer, for the table a TPU's profile carries itself; and
the eight metrics of PR 24 found and read in a root that holds only files."""

import json
from pathlib import Path

import pytest

from benchmark import xscope, xtrace
from benchmark.manifest import load_cell, load_manifest
from tests.benchmark.accepted import holds_at_least
from tests.benchmark.toy import REPO, make_toy_root

CELL = "train-2p7b-4k"
PROGRAM = "train_step"
METRICS = {  # per whole execution of the hand-made trace below
    "train_fwd_ms": 500.0, "train_bwd_ms": 200.0, "train_optimizer_ms": 200.0, "train_attn_ms": 200.0,
    "train_mlp_ms": 500.0, "train_head_loss_ms": 0.0, "train_layer_carry_ms": 0.0, "train_unattributed_pct": 0.0,
}
STEP = "jit(train_step)/grad_accumulate/while/body/closed_call"
TABLE = {
    "while.1": "jit(train_step)/grad_accumulate/while",
    "fusion.1": f"{STEP}/jvp(GPT2Module)/layer_carry/while/body/closed_call/blocks/block/mlp/W/dot_general",
    "flash_attention_fwd.2": f"{STEP}/jvp(GPT2Module)/layer_carry/while/body/closed_call/blocks/block/attn/attn_core/flash_attention_fwd/pallas_call",
    "fusion.3": f"{STEP}/transpose(jvp(GPT2Module))/layer_carry/while/body/closed_call/blocks/block/mlp/W/dot_general",
    "fusion.4": "jit(train_step)/optimizer/mul",
}  # copy-start.5 is the compiler's own: in no table
STALE = {  # the same program as a tree without this PR's scopes names it
    "while.1": "jit(train_step)/while",
    "fusion.1": "jit(train_step)/while/body/closed_call/jvp(GPT2Module)/while/body/closed_call/blocks/block/mlp/W/dot_general",
    "flash_attention_fwd.2": "jit(train_step)/while/body/closed_call/jvp(GPT2Module)/while/body/closed_call/blocks/block/attn/flash_attention_fwd/pallas_call",
    "fusion.3": "jit(train_step)/while/body/closed_call/transpose(jvp(GPT2Module))/while/body/closed_call/blocks/block/mlp/W/dot_general",
    "fusion.4": "jit(train_step)/mul",
}


def one_step(at: float) -> list[xtrace.Event]:
    """0.95 s of operations in a step of 1 s: a loop of 0.8 s that contains 0.7 s of its
    body's operations, then the optimizer, then a copy the compiler put in."""
    def event(name, start, end):
        return xtrace.Event(f"%{name} = f32[8]{{0}} op(%x)", at + start, at + end)
    return [event("while.1", 0.0, 0.8), event("fusion.1", 0.0, 0.3), event("flash_attention_fwd.2", 0.3, 0.5),
            event("fusion.3", 0.5, 0.7), event("fusion.4", 0.8, 0.9), event("copy-start.5", 0.9, 0.95)]


@pytest.fixture()
def trace() -> xtrace.Trace:
    """Two whole steps between two that the trace's edges cut (fewer operations each)."""
    ops = one_step(-1.0)[4:] + one_step(1.0) + one_step(2.0) + one_step(3.0)[:3]
    modules = [xtrace.Event(f"jit_train_step({n})", start, end) for n, (start, end) in
               enumerate([(-0.2, 0.0), (1.0, 2.0), (2.0, 3.0), (3.0, 3.3)])]
    modules.append(xtrace.Event("jit_other(9)", 0.2, 0.4))
    return xtrace.Trace([xtrace.DeviceTrace(0, ops, modules)], [])


@pytest.fixture(scope="module")
def rules() -> dict:
    return xscope.load_rules(REPO / "benchmark" / "scopes" / "train_dense.json")


def test_buckets_of_both_lists_sum_to_the_busy_time_of_the_whole_executions(trace, rules):
    found = xscope.scope_time(trace, TABLE, rules, PROGRAM)
    assert (found.executions, found.seen) == (2, 4), "the two cut executions are seen and left out"
    assert found.busy_s == pytest.approx(0.95)
    assert found.lists["pass"] == pytest.approx({"forward": 0.5, "backward": 0.2, "update": 0.2, "compiler_copies": 0.05})
    assert found.lists["component"] == pytest.approx(
        {"mlp": 0.5, "attn": 0.2, "grad_accumulate": 0.1, "optimizer": 0.1, "compiler_copies": 0.05})
    for buckets in found.lists.values():
        assert sum(buckets.values()) == pytest.approx(found.busy_s, rel=1e-12)
    assert found.unattributed_s == 0.0
    assert (found.events, found.missing) == (6, 1)
    assert found.scopes["(no op_name)/copy-start"] == pytest.approx(0.05)
    page = xscope.describe(found)
    assert "2 whole execution(s) of 4" in page and "gap to busy 0.000000 ms" in page


def test_a_stale_table_reads_as_unattributed(trace, rules):
    """Names from before the scopes: the passes are still JAX's own, but the optimizer,
    the loop and everything the table does not hold fall to no rule, and the gauge is high."""
    found = xscope.scope_time(trace, STALE, rules, PROGRAM)
    assert found.lists["pass"] == pytest.approx({"forward": 0.5, "backward": 0.2, "unattributed": 0.2, "compiler_copies": 0.05})
    assert found.unattributed_s / found.busy_s == pytest.approx(0.2 / 0.95)
    other_program = xscope.scope_time(trace, {"fusion.77": TABLE["fusion.1"]}, rules, PROGRAM)
    assert other_program.unattributed_s / other_program.busy_s == pytest.approx(0.9 / 0.95), "all but the compiler's copy"
    assert sum(other_program.lists["component"].values()) == pytest.approx(other_program.busy_s, rel=1e-12)


def test_a_trace_without_a_whole_execution_is_refused(rules):
    ops = one_step(0.0)
    cut = xtrace.Trace([xtrace.DeviceTrace(0, ops, [xtrace.Event("jit_other(1)", 0.0, 1.0)])], [])
    with pytest.raises(SystemExit, match="no whole execution"):
        xscope.scope_time(cut, TABLE, rules, PROGRAM)


def test_a_rules_list_has_to_close(tmp_path):
    open_list = {"pass": [["jvp", "forward"]], "component": [["", "unattributed"]]}
    (tmp_path / "open.json").write_text(json.dumps(open_list))
    with pytest.raises(SystemExit, match="has to end in"):
        xscope.load_rules(tmp_path / "open.json")


def test_instruction_names_and_paths():
    event = xtrace.Event("%bitcast_dynamic-update-slice_fusion.25 = (bf16[6,2]{1,0}) fusion(%p)", 0.0, 1.0)
    assert xscope.instruction_of(event.name) == "bitcast_dynamic-update-slice_fusion.25"
    assert xscope.path_of(event, {}) == "(no op_name)/bitcast_dynamic-update-slice_fusion"
    assert xscope.path_of(event, {"bitcast_dynamic-update-slice_fusion.25": "a/b"}) == "a/b"


# ------------------------------------------------------------------ the table a profile carries


def test_table_from_a_profile_reads_tf_op_of_the_programs_operations(tmp_path):
    xplane_pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    plane = space.planes.add(id=1, name="/device:TPU:0")
    for key, name in enumerate(("tf_op", "program_id", "hlo_category"), start=1):
        plane.stat_metadata[key].id, plane.stat_metadata[key].name = key, name

    def operation(key, name, display, op_name, program):
        meta = plane.event_metadata[key]
        meta.id, meta.name, meta.display_name = key, name, display
        if op_name is not None:
            meta.stats.add(metadata_id=1, str_value=op_name)
        meta.stats.add(metadata_id=2, uint64_value=program)

    operation(1, "%fusion.7 = bf16[8]{0} fusion(%p)", "fusion.7", "jit(train_step)/optimizer/mul:", 42)
    operation(2, "%copy-start.3 = (bf16[8]) copy-start(%p)", "copy-start.3", None, 42)
    operation(3, "%fusion.9 = bf16[8]{0} fusion(%p)", "fusion.9", "jit(eval_step)/jvp(GPT2Module)/add:", 43)
    for key, name in ((10, "jit_train_step(42)"), (11, "jit_eval_step(43)")):
        plane.event_metadata[key].id, plane.event_metadata[key].name = key, name
    modules = plane.lines.add(id=1, name=xtrace.MODULES_LINE)
    modules.events.add(metadata_id=10, offset_ps=0, duration_ps=10)
    modules.events.add(metadata_id=11, offset_ps=20, duration_ps=10)
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert xscope.table_from_profile(path, "train_step") == {"fusion.7": "jit(train_step)/optimizer/mul"}
    assert xscope.table_from_profile(path) == {"fusion.7": "jit(train_step)/optimizer/mul", "fusion.9": "jit(eval_step)/jvp(GPT2Module)/add"}


def test_a_profile_that_names_no_scope_gives_no_table():
    pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    trimmed = Path(__file__).resolve().parent / "traces" / "train_step_v5e.xplane.pb"  # trim_trace.py drops every stat
    assert xscope.table_from_profile(trimmed, "train_step") is None


# ------------------------------------------------------------------ the eight metrics, as files and manifest entries


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> Path:
    """The toy root, as a later PR leaves it: the metrics' files and manifest entries, and
    under its scratch directory the profile of the run that is being read."""
    root = make_toy_root(tmp_path_factory.mktemp("toy"))
    profile = root / ".bench_scratch" / CELL / "trace" / "plugins" / "profile" / "2026_09_27"
    profile.mkdir(parents=True)
    (profile / "host.xplane.pb").write_bytes(b"")
    return root


@pytest.mark.parametrize("name", sorted(METRICS))
def test_each_metric_is_found_by_its_files_and_reads_the_hand_made_trace(name, root, trace, monkeypatch):
    cell = load_cell(CELL, root)
    entry = {m["name"]: m for m in load_manifest(root)["per_layer"]}[name]
    assert name in cell.per_layer and holds_at_least(entry["workloads"], [CELL]) and entry["moves"] == "train_tokens_per_s"
    assert (entry["source"], entry["better"], entry["unit"]) == ("device_trace", "lower", "%" if name.endswith("_pct") else "ms")
    spec = cell.metric_spec(name)
    reader = cell.module("readers", spec["reader"])
    assert reader.ROOT == root, "the reader serves the root it lies under"
    assert reader.read(spec, {}, None, {}) is None, "no device trace (a CPU rehearsal): nothing to read, and no error"
    monkeypatch.setattr(xscope, "table_from_profile", lambda xplane, program: dict(TABLE))
    observed = {}
    assert reader.read(spec, observed, trace, {}) == pytest.approx(METRICS[name], abs=1e-9)
    monkeypatch.setattr(xscope, "table_from_profile", lambda xplane, program: pytest.fail("read twice"))
    assert reader.read(spec, observed, trace, {}) == pytest.approx(METRICS[name], abs=1e-9), "kept with what the run observed"


def test_the_gauge_reads_high_on_a_stale_table_and_nothing_where_the_profile_names_no_scope(root, trace, monkeypatch):
    cell = load_cell(CELL, root)
    spec = cell.metric_spec("train_unattributed_pct")
    reader = cell.module("readers", spec["reader"])
    monkeypatch.setattr(xscope, "table_from_profile", lambda xplane, program: dict(STALE))
    assert reader.read(spec, {}, trace, {}) == pytest.approx(100 * 0.2 / 0.95)
    monkeypatch.setattr(xscope, "table_from_profile", lambda xplane, program: None)
    assert reader.read(spec, {}, trace, {}) is None


def test_the_cells_that_were_there_keep_their_seven_metrics_and_gain_the_eight():
    names = load_cell(CELL, REPO).per_layer
    assert holds_at_least(names, ["train_host_stall_pct", "train_step_ms", "train_mfu_pct", "train_mfu_ref_pct",
                                  "flash_attention_roofline", "fused_ce_roofline", "device_idle_pct.train"])
    assert set(names[7:15]) == set(METRICS) and len(METRICS) == 8, "the eight follow the seven; what later PRs list follows them"


def test_describe_scopes_prints_a_page_from_a_table_given_as_hlo_text(tmp_path, capsys, monkeypatch):
    tool = REPO / "benchmark" / "tools" / "describe_scopes.py"
    import importlib.util

    module_spec = importlib.util.spec_from_file_location("describe_scopes", tool)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    table = tmp_path / "table.json"
    table.write_text(json.dumps({"fusion.180": "jit(train_step)/optimizer/mul"}))
    recorded = Path(__file__).resolve().parent / "traces" / "train_step_v5e.xplane.pb"
    monkeypatch.setattr("sys.argv", ["describe_scopes.py", str(recorded), "--program", "train_step", "--table", str(table), "--top", "5"])
    module.main()
    page = capsys.readouterr().out
    assert "1 whole execution(s) of 2" in page and "scope [primitive]" in page and "(no op_name)/" in page
