"""The full CLI loop as a user runs it: `modalities_tpu run` (pretrain) then
`modalities_tpu warmstart --last_checkpoint_info_file_path ...` (resume) as REAL
subprocesses — the reference's documented launch sequence (README warmstart flow,
reference __main__.py:112-163), not the in-process Main shortcut the other e2e
tests use. Covers TpuEnv setup, the warmstart_env resolver injection from
last_checkpoint_info.json, and the rich/save_to_disc subscriber wiring under the
CLI entry."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from modalities_tpu.dataloader.packed_data import write_pbin_file
from tests.conftest import xla_flags

REPO = Path(__file__).parent.parent.parent
# phase 1 is the pp2 x dp2 x tp2 pretrain — the warmstart config's training target
# (24576 = 8192 seen under dp2 + 4 more steps x 4096 under dp8) is keyed to it
RUN_CONFIG = REPO / "configs" / "config_lorem_ipsum_tpu_pp_tp.yaml"
WARMSTART_CONFIG = REPO / "configs" / "config_lorem_ipsum_tpu_warmstart.yaml"


@pytest.fixture
def workdir(tmp_path):
    rng = np.random.default_rng(0)
    (tmp_path / "data").mkdir()
    write_pbin_file(
        tmp_path / "data" / "lorem_ipsum.pbin",
        iter([rng.integers(0, 256, size=34000)]),
        token_size_in_bytes=2,
    )
    return tmp_path


def _cli(args, cwd):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = xla_flags(8)
    env["PYTHONPATH"] = str(REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "modalities_tpu", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, f"CLI {args[0]} failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-3000:]}"
    return proc


def _train_lines(workdir, exclude=()):
    """Train records of the newest experiment dir (the CLI generates the id)."""
    root = workdir / "data" / "experiments"
    dirs = [p for p in root.iterdir() if p.is_dir() and p.name not in exclude]
    assert len(dirs) == 1, dirs
    results = dirs[0] / "evaluation_results.jsonl"
    lines = [json.loads(line) for line in results.read_text().splitlines()]
    return dirs[0].name, [r for r in lines if r["dataloader_tag"] == "train"]


def _timeline(workdir, experiment_id):
    """The run's sink and goodput summary (PR 34: the timeline from process start)."""
    folder = workdir / "data" / "experiments" / experiment_id / "telemetry"
    events = [json.loads(line) for line in (folder / "telemetry_rank_0.jsonl").read_text().splitlines()]
    return events, json.loads((folder / "goodput_summary.json").read_text())


def _assert_timeline_starts_with_the_process(events, summary, restored: bool):
    spans = [e for e in events if e["event"] == "span"]
    # the set-up happened before `Main.run` made the run's Telemetry active: its spans came from the process log
    assert [e["name"] for e in spans[:2]] == ["backend_start", "build_components"]
    assert all(e["timeline"] and e["parent"] is None and e["step"] is None for e in spans[:2])
    by_name = {e["name"]: e for e in reversed(spans)}  # the first of each name
    assert by_name["state_init"]["parent"] == "init" and by_name["first_step"]["step"] >= 1
    assert ("checkpoint_restore" in by_name) == restored
    # `start_s` counts from the process log's origin, the package's import: the entry point's spans open within
    # seconds of it and in order, and the ledger's wall clock covers them all
    starts = [by_name[name]["start_s"] for name in ("backend_start", "build_components", "init", "first_step")]
    assert 0 < starts[0] and starts == sorted(starts)
    last_end = max(e["start_s"] + e["dur_s"] for e in spans)
    assert last_end <= summary["wall_s"] <= last_end + 5.0, "wall_s starts at the process log's origin"
    setup = sum(by_name[name]["self_s"] for name in ("backend_start", "build_components"))
    assert summary["buckets"]["init"] >= setup + by_name["init"]["dur_s"] - 1e-3
    assert sum(summary["buckets"].values()) == pytest.approx(summary["wall_s"], abs=1e-3)
    compiles = [e for e in events if e["event"] == "compile"]
    assert compiles and all(0 < e["end_s"] <= summary["wall_s"] for e in compiles)


def test_cli_run_then_warmstart_subprocess_loop(workdir):
    _cli(
        ["run", "--config_file_path", str(RUN_CONFIG),
         "--experiments_root_path", str(workdir / "data" / "experiments")],
        cwd=workdir,
    )
    eid1, train = _train_lines(workdir)
    assert train[-1]["num_train_steps_done"] == 8
    _assert_timeline_starts_with_the_process(*_timeline(workdir, eid1), restored=False)
    info_path = workdir / "data" / "checkpoints" / "last_checkpoint_info.json"
    info = json.loads(info_path.read_text())
    assert "seen_steps_8-" in info["checkpoint_folder_path"]

    _cli(
        ["warmstart", "--config_file_path", str(WARMSTART_CONFIG),
         "--last_checkpoint_info_file_path", str(info_path),
         "--experiments_root_path", str(workdir / "data" / "experiments")],
        cwd=workdir,
    )
    eid2, train2 = _train_lines(workdir, exclude=(eid1,))
    _assert_timeline_starts_with_the_process(*_timeline(workdir, eid2), restored=True)
    assert train2[0]["num_train_steps_done"] > 8, "warmstart restarted instead of resuming"
    assert train2[-1]["num_train_steps_done"] == 12
    assert all(np.isfinite(r["losses"]["train loss avg"]) for r in train2)
    # the resume kept counting tokens from the pretrain run (8 steps x 8 mbs x
    # 64 seq x 2 dp of phase 1 = 8192, then 4 steps x 4096 under dp8)
    assert train2[-1]["metrics"]["consumed tokens"] == 8192 + 4 * 4096
