"""The reduction from a profiler trace to metrics, on two small traces: one recorded on a
TPU v5e (one train step of `train-2p7b-4k` and the head of the next, trimmed with
benchmark/tools/trim_trace.py; PR 23) and one written by hand, where every number can be
worked out on paper."""

from pathlib import Path

import pytest

from benchmark import xtrace
from benchmark.stats import merge, subtract, union_length

DATA = Path(__file__).resolve().parent / "traces"
KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkv",
           "fused_ce_fwd", "fused_ce_bwd_dh", "fused_ce_bwd_dw")


def test_interval_arithmetic():
    assert union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert merge([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]


@pytest.fixture(scope="module")
def recorded():
    return xtrace.load(DATA / "train_step_v5e.xplane.pb")


def test_recorded_trace_has_one_device_its_programs_and_the_programs_spans(recorded):
    assert len(recorded.devices) == 1
    device = recorded.devices[0]
    assert len(device.ops) > 2000 and len(device.async_ops) > 100
    runs = xtrace.module_runs(recorded, "train_step")
    assert len(runs) == 2 and runs[0].seconds == pytest.approx(0.34034, abs=1e-4), "one whole step of 340 ms"
    assert {"data_wait", "train_step", "metrics_fetch"} <= {e.name for e in recorded.host_spans}
    assert xtrace.breakdown(recorded)["idle_gaps"][0][0] == "metrics_fetch", "named as the program names it, not `np.asarray(jax.Array)` inside it"


def test_busy_union_own_time_and_idle_share_agree(recorded):
    device = recorded.devices[0]
    busy = xtrace.busy_seconds(recorded)
    start, end = recorded.window
    assert busy == pytest.approx(union_length((e.start, e.end) for e in device.ops))
    # a loop's event contains its body's events: own times must add up to the union, not more
    assert sum(own for _, own in xtrace.self_seconds(device.ops)) == pytest.approx(busy, rel=1e-9)
    assert busy < sum(e.seconds for e in device.ops)
    assert xtrace.idle_share(recorded) == pytest.approx(1 - busy / (end - start))
    assert 0 <= xtrace.idle_share(recorded) < 0.001, "one program after the other: the chip never waits"


def test_kernels_are_found_by_the_names_their_pallas_calls_carry(recorded):
    by_label = xtrace.time_by_label(recorded)
    assert set(KERNELS) <= set(by_label)
    calls = {k: sum(len(d) for d in xtrace.label_events(recorded, f"^{k}$")) for k in KERNELS}
    # 6 layers: one whole step (6 forward, 6 + 6 backward, one of each loss kernel) and 4 forwards of the next
    assert calls == {"flash_attention_fwd": 10, "flash_attention_bwd_dq": 6, "flash_attention_bwd_dkv": 6,
                     "fused_ce_fwd": 1, "fused_ce_bwd_dh": 1, "fused_ce_bwd_dw": 1}
    assert by_label["flash_attention_bwd_dkv"] == pytest.approx(0.03005, abs=1e-4)
    assert by_label["fused_ce_fwd"] == pytest.approx(0.01514, abs=1e-4)
    top = [name for name, _ in xtrace.breakdown(recorded)["device_ops"]]
    assert top[0] == "fusion" and len(top) == 10 and all(len(name) < 64 for name in top)


def test_instruction_names_are_cut_to_labels():
    label = lambda name: xtrace.op_label(xtrace.Event(name, 0.0, 1.0))  # noqa: E731
    assert label("%fusion.123 = bf16[2,4096]{1,0} fusion(%p), kind=kOutput") == "fusion"
    assert label("%transpose_jvp_fused_ce_bwd_dw__.2 = bf16[50432,2560] custom-call(") == "fused_ce_bwd_dw"
    assert label("%flash_attention_bwd_dkv.11 = (bf16[2,32,4096,80]") == "flash_attention_bwd_dkv"
    assert label("%all-gather-start.3 = (bf16[1,2]) all-gather-start(") == "all-gather-start"
    assert label("%copy-done = bf16[2] copy-done(") == "copy-done"
    assert label("%fusion.180.remat_compressed = bf16[2] fusion(") == "fusion.remat_compressed"


def _xspace(planes: dict) -> bytes:
    """An XSpace from {plane: {line: [(name, start_us, duration_us), ...]}}; a plane's lines may be a list of
    (line, events) pairs instead, since the host's Python threads all carry one name."""
    from jax.profiler import ProfileData

    text = []
    for plane_id, (plane, lines) in enumerate(planes.items()):
        lines = list(lines.items()) if isinstance(lines, dict) else lines
        names = sorted({name for _, events in lines for name, _, _ in events})
        body = []
        for line_id, (line, events) in enumerate(lines):
            rows = "".join(
                f" events {{ metadata_id: {names.index(name) + 1} offset_ps: {int(start * 1e6)} duration_ps: {int(dur * 1e6)} }}"
                for name, start, dur in events)
            body.append(f' lines {{ id: {line_id} name: "{line}" timestamp_ns: 0{rows} }}')
        metadata = "".join(f' event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: "{n}" }} }}' for i, n in enumerate(names))
        text.append(f'planes {{ id: {plane_id} name: "{plane}"{"".join(body)}{metadata} }}')
    return ProfileData.text_proto_to_serialized_xspace("\n".join(text))


@pytest.fixture(scope="module")
def by_hand(tmp_path_factory):
    """Two chips, 100 us. Chip 0: compute 0-40, an all-reduce from 30 that the core waits
    for from 40 to 60 (so 20 us of it are exposed), nothing from 60 to 80 while the host
    fetches metrics, compute 80-100. Chip 1: compute 0-50, the same all-reduce in flight
    30-60 and waited for 50-60 (10 us exposed), compute 60-100. The host as the program's
    loop leaves it: the dispatch (`train_step` with JAX's own `PjitFunction` inside it),
    then `metrics_fetch` with JAX's `np.asarray(jax.Array)` inside it, and on a second
    Python thread the feeder's `transfer`, open all the while, round its `shard_args`."""
    device = lambda compute, done, in_flight: {  # noqa: E731
        "XLA Ops": [("%while.1 = () while(", 0, 100)] + [(f"%fusion.{i} = f32[] fusion(", s, d) for i, (s, d) in enumerate(compute)]
        + [("%all-reduce-done.1 = f32[8] all-reduce-done(", *done)],
        "Async XLA Ops": [("%all-reduce-start.1 = f32[8] all-reduce-start(", *in_flight)],
        "XLA Modules": [("jit_train_step(1)", 0, 100)],
    }
    space = _xspace({
        "/device:TPU:0": device([(0, 40), (80, 20)], (40, 20), (30, 30)),
        "/device:TPU:1": device([(0, 50), (60, 40)], (50, 10), (30, 30)),
        "/host:CPU": [("python3", [("train_step", 54, 3), ("PjitFunction(train_step)", 55, 1), ("metrics_fetch", 58, 24),
                                   ("np.asarray(jax.Array)", 59, 22), ("data_wait", 95, 2)]),
                      ("python3", [("transfer", 0, 100), ("shard_args", 61, 4)]),
                      ("tf_worker/7", [("tpu::System::Execute", 0, 100)])],
    })
    path = tmp_path_factory.mktemp("trace") / "by_hand.xplane.pb"
    path.write_bytes(space)
    return xtrace.load(path)


def test_by_hand_busy_idle_and_own_time(by_hand):
    assert [d.ordinal for d in by_hand.devices] == [0, 1]
    assert by_hand.window == pytest.approx((0.0, 100e-6))
    # the loop's event spans everything: the union is the whole window, its own time the 20 us gap
    assert xtrace.busy_seconds(by_hand) == pytest.approx(100e-6)
    own = xtrace.time_by_label(by_hand)
    assert own["fusion"] == pytest.approx((60e-6 + 90e-6) / 2)
    assert own["all-reduce-done"] == pytest.approx((20e-6 + 10e-6) / 2)
    assert own["while"] == pytest.approx((20e-6 + 0.0) / 2)


def test_by_hand_gap_goes_to_the_host_span_open_in_it(by_hand):
    without_loop = xtrace.Trace([xtrace.DeviceTrace(0, [e for e in by_hand.devices[0].ops if "while" not in e.name],
                                                    by_hand.devices[0].modules)], by_hand.host_spans)
    assert xtrace.idle_share(without_loop) == pytest.approx(0.2)
    gaps = dict(xtrace.idle_gaps(without_loop))
    # 60-80 us: `metrics_fetch` (58-82) covers all of it, and so does JAX's `np.asarray(jax.Array)` (59-81) inside it: the
    # program's span, which encloses JAX's, names the gap; the feeder's `transfer` covers it too, from another thread and
    # for far longer than the loop's span does
    assert gaps == {"metrics_fetch": pytest.approx(20e-6)}
    assert {e.name for e in by_hand.host_spans} == {"train_step", "PjitFunction(train_step)", "metrics_fetch", "np.asarray(jax.Array)",
                                                    "data_wait", "transfer", "shard_args"}, "Python threads only"
    assert len({e.thread for e in by_hand.host_spans}) == 2, "a span keeps the thread it lies on"


def test_a_gap_is_named_by_the_programs_span_and_by_jaxs_only_where_no_span_of_the_program_covers_it():
    span = lambda name, start, end, thread=0: xtrace.Event(name, start, end, thread)  # noqa: E731
    loop = [span("train_step", 0.0, 3.0), span("PjitFunction(train_step)", 0.5, 2.5), span("metrics_fetch", 4.0, 30.0),
            span("np.asarray(jax.Array)", 4.5, 29.5), span("np.asarray(jax.Array)", 40.0, 41.0)]
    feeder = [span("transfer", 0.0, 100.0, 1), span("DevicePutWithSharding", 10.0, 12.0, 1)]
    name = lambda a, b, spans=loop + feeder: xtrace._span_over((a, b), spans)  # noqa: E731
    assert name(1.0, 2.0) == "train_step" and name(5.0, 25.0) == "metrics_fetch"
    assert name(10.5, 11.5) == "metrics_fetch", "the feeder's `transfer` encloses its `DevicePutWithSharding` and outlasts the loop's span: the loop's names it"
    assert name(40.2, 40.8) == "np.asarray(jax.Array)", "no span of the program covers it: JAX's own names it"
    assert name(3.2, 3.8) == "transfer" and name(3.2, 3.8, loop) == "(no span)"
    # a span that covers more of the gap wins over one that encloses it on paper and covers less
    assert name(29.0, 31.0, loop) == "metrics_fetch" and name(2.0, 4.4, loop) == "train_step"


def test_by_hand_exposed_collective(by_hand):
    # chip 0: in flight or waited for 30-60, compute until 40 -> 20 us; chip 1: compute until 50 -> 10 us
    assert xtrace.exposed_collective_seconds(by_hand) == pytest.approx((20e-6 + 10e-6) / 2)
