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
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
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


def test_cli_run_then_warmstart_subprocess_loop(workdir):
    _cli(
        ["run", "--config_file_path", str(RUN_CONFIG),
         "--experiments_root_path", str(workdir / "data" / "experiments")],
        cwd=workdir,
    )
    eid1, train = _train_lines(workdir)
    assert train[-1]["num_train_steps_done"] == 8
    info_path = workdir / "data" / "checkpoints" / "last_checkpoint_info.json"
    info = json.loads(info_path.read_text())
    assert "seen_steps_8-" in info["checkpoint_folder_path"]

    _cli(
        ["warmstart", "--config_file_path", str(WARMSTART_CONFIG),
         "--last_checkpoint_info_file_path", str(info_path),
         "--experiments_root_path", str(workdir / "data" / "experiments")],
        cwd=workdir,
    )
    _, train2 = _train_lines(workdir, exclude=(eid1,))
    assert train2[0]["num_train_steps_done"] > 8, "warmstart restarted instead of resuming"
    assert train2[-1]["num_train_steps_done"] == 12
    assert all(np.isfinite(r["losses"]["train loss avg"]) for r in train2)
    # the resume kept counting tokens from the pretrain run (8 steps x 8 mbs x
    # 64 seq x 2 dp of phase 1 = 8192, then 4 steps x 4096 under dp8)
    assert train2[-1]["metrics"]["consumed tokens"] == 8192 + 4 * 4096
