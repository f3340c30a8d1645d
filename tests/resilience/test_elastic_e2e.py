"""Elastic topology-change e2es: the ISSUE 6 acceptance scenarios, run through
the full config-driven app.

(a) mesh A -> mesh B resume: train on dp8, warmstart the SAME checkpoint onto a
    dp4 mesh (local batch doubled so the global batch — and therefore the data
    stream per optimizer step — is unchanged). The Orbax reshard-at-load path
    lays the dp8 shards onto the dp4 mesh; losses must match an uninterrupted
    dp8 twin to fp-reduction tolerance (rtol 1e-5).
(b) 2-process host_loss chaos: one whole host (supervisor + child) dies
    permanently mid-run; the survivor's heartbeat converts the collective hang
    into a resumable exit and its supervisor, with `--min_hosts 1`, rewrites
    the warmstart config for the shrunk world and finishes the run
    single-process on half the devices.

Both are `slow`-marked: each costs tens of seconds to minutes of compile+train,
which does not fit the tier-1 wall-time budget. The cheap unit-level versions
(Orbax reshard restore, vote/ladder/rewrite logic) run in tier-1 under
tests/checkpointing/test_topology.py and tests/resilience/test_{elastic,
supervisor,coordination}.py.
"""

import json
import os
import signal
import socket
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
import yaml

from modalities_tpu.checkpointing.topology import TOPOLOGY_FILE_NAME
from modalities_tpu.dataloader.packed_data import write_pbin_file
from modalities_tpu.main import Main
from modalities_tpu.resilience import PreemptionShutdown
from modalities_tpu.resilience.events import counts_since, snapshot_counts
from modalities_tpu.resilience.faults import arm_faults
from modalities_tpu.resilience.manifest import MANIFEST_FILE_NAME, resolve_resume_folder
from tests.conftest import xla_flags

CONFIG = Path(__file__).parent.parent.parent / "configs" / "config_lorem_ipsum_tpu.yaml"
WARMSTART_CONFIG = (
    Path(__file__).parent.parent.parent / "configs" / "config_lorem_ipsum_tpu_warmstart.yaml"
)

pytestmark = pytest.mark.slow


@pytest.fixture
def workdir(tmp_path, monkeypatch):
    rng = np.random.default_rng(0)
    (tmp_path / "data").mkdir()
    tokens = rng.integers(0, 256, size=56000)
    write_pbin_file(tmp_path / "data" / "lorem_ipsum.pbin", iter([tokens]), token_size_in_bytes=2)
    monkeypatch.chdir(tmp_path)
    return tmp_path


def _write_config(workdir, name, text):
    path = workdir / name
    path.write_text(text)
    return path


def _run(config_path, experiment_id, workdir, resolver=None):
    main = Main(
        config_path,
        experiments_root_path=workdir / "data" / "experiments",
        experiment_id=experiment_id,
        additional_resolver_funs=resolver,
    )
    main.run(main.build_components())
    results = workdir / "data" / "experiments" / experiment_id / "evaluation_results.jsonl"
    return [json.loads(line) for line in results.read_text().splitlines()]


def _train_lines(lines):
    return [r for r in lines if r["dataloader_tag"] == "train"]


# ------------------------------------------- (a) mesh A -> mesh B warmstart


def test_mesh_change_resume_matches_uninterrupted_twin(workdir):
    """dp8 checkpoint at step 8 -> dp4 warmstart to step 12. Doubling the local
    micro-batch keeps the global batch at 64 samples/step, and the sampler's
    GLOBAL skip semantics keep the per-step sample sets identical, so the only
    difference from the dp8 twin is fp reduction order."""
    # uninterrupted dp8 twin over the full 12-step schedule
    twin_config = _write_config(
        workdir,
        "config_12_steps.yaml",
        CONFIG.read_text()
        .replace("num_target_tokens: 32768", "num_target_tokens: 49152")
        .replace("num_target_steps: 8", "num_target_steps: 12"),
    )
    ref = _train_lines(_run(twin_config, "ref", workdir))
    assert ref[-1]["num_train_steps_done"] == 12
    ref_by_step = {r["num_train_steps_done"]: r for r in ref}

    # mesh A: the dp8 run under the SAME 12-step schedule (so the twin's LR
    # trajectory matches), preempted right after its step-8 checkpoint
    arm_faults("sigterm_at_step@8")
    main = Main(
        twin_config,
        experiments_root_path=workdir / "data" / "experiments",
        experiment_id="mesh_a",
    )
    with pytest.raises(PreemptionShutdown, match="step 8"):
        main.run(main.build_components())
    resume_folder = resolve_resume_folder(workdir / "data" / "checkpoints" / "last_checkpoint_info.json")
    assert "seen_steps_8-" in resume_folder.name
    assert (resume_folder / TOPOLOGY_FILE_NAME).is_file()
    saved_topology = json.loads((resume_folder / TOPOLOGY_FILE_NAME).read_text())
    assert saved_topology["mesh_axes"] == {"dp_shard": 8}

    # mesh B: same global batch (4 ranks x 16 local = 64), half the devices
    mesh_b_config = _write_config(
        workdir,
        "config_warmstart_dp4.yaml",
        WARMSTART_CONFIG.read_text()
        .replace("num_target_tokens: 24576", "num_target_tokens: 49152")
        .replace("data_parallel_shard_degree: 8", "data_parallel_shard_degree: 4")
        .replace("world_size: 8", "world_size: 4")
        .replace("local_train_micro_batch_size: 8", "local_train_micro_batch_size: 16"),
    )
    snapshot = snapshot_counts()
    resumed = _train_lines(
        _run(
            mesh_b_config,
            "mesh_b",
            workdir,
            resolver={"warmstart_env": lambda key: str(resume_folder)},
        )
    )

    # the mismatch was DETECTED (one elastic/reshard event), not silently eaten,
    # and the manifest still verified (no rollback, no verification downgrade)
    events = counts_since(snapshot)
    assert events.get("elastic") == 1
    assert "rollback" not in events

    # resumed at step 8, finished at 12, token accounting continuous
    assert resumed[0]["num_train_steps_done"] == 10
    assert resumed[-1]["num_train_steps_done"] == 12
    for line in resumed:
        twin = ref_by_step[line["num_train_steps_done"]]
        assert line["metrics"]["consumed tokens"] == twin["metrics"]["consumed tokens"]
        np.testing.assert_allclose(
            line["losses"]["train loss avg"], twin["losses"]["train loss avg"], rtol=1e-5
        )
        np.testing.assert_allclose(
            line["losses"]["train loss last"], twin["losses"]["train loss last"], rtol=1e-5
        )


# --------------------------------- (b) host loss -> degraded elastic resume


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _require_mp_cpu_collectives() -> None:
    from tests.parallel import test_multiprocess as _mp

    _mp._require_mp_cpu_collectives()


def test_host_loss_resumes_elastic_on_shrunk_topology(tmp_path):
    """Two supervisors (host_count=2) over one shared ring. `host_loss@6:1`
    SIGKILLs host 1's supervisor and child for good. Host 0's child detects the
    dead peer (heartbeat) and exits resumable; its supervisor's resume vote
    misses quorum, and `--min_hosts 1` turns that into an elastic resume: the
    warmstart config is rewritten for world 4 and the child finishes the run as
    a SINGLE process on this host's 4 devices."""
    _require_mp_cpu_collectives()

    rng = np.random.default_rng(0)
    (tmp_path / "data").mkdir()
    tokens = rng.integers(0, 256, size=56000)
    write_pbin_file(tmp_path / "data" / "lorem_ipsum.pbin", iter([tokens]), token_size_in_bytes=2)

    # 12-step schedule + fast peer-death detection (defaults are 5s/30s)
    cold_config = tmp_path / "config_cold.yaml"
    cold_config.write_text(
        CONFIG.read_text()
        .replace("num_target_tokens: 32768", "num_target_tokens: 49152")
        .replace("num_target_steps: 8", "num_target_steps: 12")
        .replace(
            "    anomaly_policy: raise",
            "    anomaly_policy: raise\n"
            "    heartbeat_interval_s: 0.5\n"
            "    peer_deadline_s: 6.0",
        )
    )
    warm_config = tmp_path / "config_warm.yaml"
    warm_config.write_text(WARMSTART_CONFIG.read_text())

    ring = tmp_path / "data" / "checkpoints"
    votes = tmp_path / "votes"
    port = _free_port()

    def _spawn_host(host_id: int) -> subprocess.Popen:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = xla_flags(4)
        env["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["JAX_NUM_PROCESSES"] = "2"
        env["JAX_PROCESS_ID"] = str(host_id)
        env["MODALITIES_TPU_FAULTS"] = "host_loss@6:1"
        env["PYTHONPATH"] = str(Path(__file__).parent.parent.parent)
        cmd = [
            sys.executable, "-m", "modalities_tpu", "run",
            "--config_file_path", str(cold_config),
            "--experiments_root_path", str(tmp_path / "data" / "experiments"),
            "--resilient",
            "--last_checkpoint_info_file_path", str(ring / "last_checkpoint_info.json"),
            "--warmstart_config_file_path", str(warm_config),
            "--max_restarts", "3",
            "--backoff_base_s", "0.2",
            "--host_count", "2",
            "--host_id", str(host_id),
            "--min_hosts", "1",
            "--resume_vote_deadline_s", "8",
            "--coordination_dir_path", str(votes),
        ]
        return subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=tmp_path,
        )

    procs = [_spawn_host(0), _spawn_host(1)]
    results = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        if "Multiprocess computations aren't implemented on the CPU backend" in err:
            pytest.skip("jaxlib: no multiprocess CPU collectives")
        results.append((p.returncode, out, err))

    # host 1 is GONE: its supervisor was SIGKILLed by the fault
    assert results[1][0] == -signal.SIGKILL, results[1][2][-3000:]
    # host 0 finished the run despite losing its peer for good
    assert results[0][0] == 0, results[0][2][-3000:]

    # host 0's supervisor rewrote the warmstart config for the shrunk world
    rewrites = sorted(votes.glob("elastic_warmstart_a*_h0.yaml"))
    assert rewrites, sorted(p.name for p in votes.iterdir())
    rewritten = yaml.safe_load(rewrites[-1].read_text())
    assert rewritten["device_mesh"]["config"]["world_size"] == 4
    assert rewritten["device_mesh"]["config"]["data_parallel_shard_degree"] == 4

    # the shrunk run trained to the 12-step target and sealed its checkpoint
    final = [p for p in ring.glob("eid_*") if "seen_steps_12-" in p.name]
    assert len(final) == 1, sorted(p.name for p in ring.iterdir())
    assert (final[0] / MANIFEST_FILE_NAME).is_file()
    assert (final[0] / TOPOLOGY_FILE_NAME).is_file()
    # ...under the SHRUNK topology
    topo = json.loads((final[0] / TOPOLOGY_FILE_NAME).read_text())
    assert topo["mesh_axes"] == {"dp_shard": 4}
