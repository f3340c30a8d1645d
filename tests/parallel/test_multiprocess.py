"""Multi-process (multi-host-shaped) validation: two jax.distributed CPU processes,
4 virtual devices each, drive put_batch's `make_array_from_process_local_data` branch
and the per-host data split; the global result must match single-process exactly.
(Reference: the multi-rank tiers of tests/run_distributed_tests.sh:36-50.)"""

import os
import socket
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from tests.conftest import CPU_COMPILE_FLAGS, xla_flags

WORKER = Path(__file__).parent / "multiprocess_worker.py"

# Some jaxlib builds cannot run cross-process collectives on the CPU backend at
# all ("Multiprocess computations aren't implemented on the CPU backend") — an
# environment limitation, not a code defect, so the 2-process tier skips with the
# evidence instead of failing.
_MP_CPU_UNSUPPORTED = "Multiprocess computations aren't implemented on the CPU backend"
_MP_CPU_PROBE: list[bool] = []  # memoized once per session


def _skip_if_mp_cpu_unsupported(err: str) -> None:
    if _MP_CPU_UNSUPPORTED in err:
        pytest.skip(f"jaxlib: {_MP_CPU_UNSUPPORTED}")


_PROBE_SRC = """
import sys
import jax
jax.distributed.initialize(f"127.0.0.1:{sys.argv[1]}", 2, int(sys.argv[2]))
from jax.experimental import multihost_utils
multihost_utils.assert_equal(jax.numpy.zeros(()), "probe")
print("COMM OK")
"""


def _require_mp_cpu_collectives() -> None:
    """Skip the whole 2-process tier BEFORE its expensive single-process oracles
    when this jaxlib cannot run cross-process CPU collectives at all. One cheap
    psum probe (two bare interpreters) per session, memoized."""
    if not _MP_CPU_PROBE:
        env = {**_clean_env(), "XLA_FLAGS": xla_flags(4)}
        port = _free_port()
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", _PROBE_SRC, str(port), str(pid)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
            )
            for pid in range(2)
        ]
        supported = True
        for p in procs:
            _, err = p.communicate(timeout=120)
            if _MP_CPU_UNSUPPORTED in err:
                supported = False
        _MP_CPU_PROBE.append(supported)
    if not _MP_CPU_PROBE[0]:
        pytest.skip(f"jaxlib: {_MP_CPU_UNSUPPORTED}")


def _clean_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = CPU_COMPILE_FLAGS  # the worker adds its own device count (4 per process)
    env["PYTHONPATH"] = str(WORKER.parent.parent.parent)
    return env


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _parse_losses(out: str) -> list[float]:
    losses = [float(line.split()[1]) for line in out.splitlines() if line.startswith("LOSS ")]
    assert losses, f"no LOSS line in output:\n{out}"
    return losses


def _start_single(mode: str, env: dict) -> subprocess.Popen:
    """One process that recreates the GLOBAL 8-device mesh (2 x 4 in a pair). It runs beside the pair it is the oracle of:
    a test's seconds are its processes' start-ups in a row, and the two sides need nothing of each other."""
    return subprocess.Popen(
        [sys.executable, str(WORKER), "single", mode],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env={**env, "MP_WORKER_DEVICES": "8"},
    )


def _start_pair(mode: str, env: dict) -> list[subprocess.Popen]:
    port = _free_port()
    return [
        subprocess.Popen(
            [sys.executable, str(WORKER), str(port), str(pid), "2", mode],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )
        for pid in range(2)
    ]


def _single_losses(proc: subprocess.Popen) -> list[float]:
    out, err = proc.communicate(timeout=600)
    assert proc.returncode == 0, err[-3000:]
    return _parse_losses(out)


def _pair_losses(procs: list[subprocess.Popen]) -> list[list[float]]:
    outs, eids = [], []
    for p in procs:
        out, err = p.communicate(timeout=600)
        _skip_if_mp_cpu_unsupported(err)
        assert p.returncode == 0, err[-3000:]
        assert "COMM OK" in out, f"multi-process communication test failed:\n{out}"
        eids += [line.split(None, 1)[1] for line in out.splitlines() if line.startswith("EID ")]
        outs.append(_parse_losses(out))
    # experiment-id sync: process 0 generated it, every process adopted it
    assert len(eids) == 2 and eids[0] == eids[1], eids
    return outs


def _run_two_process_vs_single(mode: str):
    _require_mp_cpu_collectives()
    env = _clean_env()
    single, pair = _start_single(mode, env), _start_pair(mode, env)
    (oracle,), outs = _single_losses(single), _pair_losses(pair)
    # every process reports the same global loss, equal to the single-process oracle
    assert outs[0] == outs[1] and len(outs[0]) == 1
    assert abs(outs[0][0] - oracle) < 1e-5, (outs, oracle)


def test_two_process_put_batch_matches_single_process():
    # each process fed only its own rows, so agreement proves the local-shard
    # assembly (make_array_from_process_local_data) is right
    _run_two_process_vs_single("dp")


def test_multiprocess_orbax_checkpoint_save_and_crosstopology_resume(tmp_path):
    """The pod-checkpointing contract (VERDICT r4 #3): 2 jax.distributed processes
    (4 devices each) train 3 steps and save through the REAL CheckpointSaving stack
    (per-process Orbax shard writes, primary-host resume pointer); the run then
    resumes (a) with 2 processes and (b) single-process on the same 8-device mesh.
    Both resumed loss curves must continue an uninterrupted single-process oracle
    EXACTLY — save/restore is transparent to training, across process topologies."""
    _require_mp_cpu_collectives()
    env = {**_clean_env(), "MP_CKPT_DIR": str(tmp_path)}

    # phase A: 2-process train + collective save, beside the oracle's five uninterrupted steps
    single, pair = _start_single("ckpt_oracle", env), _start_pair("ckpt_save", env)
    oracle, outs = _single_losses(single), _pair_losses(pair)
    assert len(oracle) == 5
    assert outs[0] == outs[1]
    assert np.allclose(outs[0], oracle[:3], atol=1e-5), (outs[0], oracle[:3])
    folders = [p.name for p in tmp_path.iterdir() if p.is_dir()]
    assert any("seen_steps_3-seen_tokens_384-" in f for f in folders), folders
    assert (tmp_path / "last_checkpoint_info.json").exists()

    # phase B, both readers of the one checkpoint at once: (1) resume with the SAME process topology (2 x 4 devices),
    # (2) resume SINGLE-process on the 8-device mesh (process count changed)
    single2, pair2 = _start_single("ckpt_resume", env), _start_pair("ckpt_resume", env)
    resumed_single, outs2 = _single_losses(single2), _pair_losses(pair2)
    assert outs2[0] == outs2[1]
    assert np.allclose(outs2[0], oracle[3:], atol=1e-5), (outs2[0], oracle[3:])
    assert np.allclose(resumed_single, oracle[3:], atol=1e-5)


def test_two_process_hsdp_replicate_axis_crosses_process_boundary():
    """HSDP (dp_replicate=2 x dp_shard=4) over 2 processes: each process IS one
    replica group, so the gradient all-reduce over dp_replicate crosses the
    process boundary and each process feeds only its replica group's rows. Global
    loss must equal the single-process HSDP oracle exactly."""
    _run_two_process_vs_single("hsdp")


def test_two_process_ring_attention_crosses_process_boundary():
    """cp spanning ALL 8 devices of 2 jax.distributed processes: the ring's k/v
    ppermute hops cross the process boundary (the DCN tier of SURVEY §5.7 context
    parallelism — unreachable from any single-process mesh), and the global loss
    must match the single-process cp8 oracle exactly."""
    _run_two_process_vs_single("cp")


@pytest.mark.slow  # two subprocess compiles (~25s) of a stable subsystem; tier-1
# wall-time budget (see docs) — run with -m slow
def test_single_process_cp_feeder_async_matches_sync():
    """Async vs sync feeder over an 8-device cp mesh in ONE process: put_batch's
    cp seq-dim slicing (`local_seq_slice`) runs on the feeder's background thread
    and must be loss-exact vs the inline path — the runnable half of the feeder
    cp contract even on jaxlibs without multiprocess CPU collectives."""
    procs = [_start_single("feeder_cp", {**_clean_env(), "MP_FEEDER_PREFETCH": prefetch}) for prefetch in ("0", "2")]
    outs = [_single_losses(p) for p in procs]
    assert len(outs[0]) == 3
    assert outs[0] == outs[1], outs


def test_two_process_cp_feeder_async_matches_sync_and_single_process():
    """DeviceFeeder equivalence across processes (async-input-pipeline tentpole):
    a single-process SYNC run (prefetch 0, 8-device cp mesh) is the oracle; the
    2-process run stages every batch through the ASYNC feeder (prefetch 2 — the
    cp-aware seq slice + make_array_from_process_local_data run in a background
    thread on each process). Both processes must agree with each other exactly
    and with the sync oracle to 1e-5 — guarding the feeder's multi-host
    enqueue-order contract and put_batch's `local_seq_slice`."""
    _require_mp_cpu_collectives()
    env = _clean_env()
    single = _start_single("feeder_cp", {**env, "MP_FEEDER_PREFETCH": "0"})
    pair = _start_pair("feeder_cp", {**env, "MP_FEEDER_PREFETCH": "2"})
    oracle, outs = _single_losses(single), _pair_losses(pair)
    assert len(oracle) == 3
    assert outs[0] == outs[1]
    assert np.allclose(outs[0], oracle, atol=1e-5), (outs, oracle)


def test_two_process_pipeline_mesh_crosses_process_boundary():
    """pp2 x dp2 spanning two jax.distributed processes: the scheduled executor's
    activation/cotangent ppermutes and the head psum-broadcast cross the process
    boundary (the DCN tier of SURVEY §5.8), and get_data_loading_info must report
    ONE loading rank — every process owns all dp coordinates, so each feeds the
    full batch (asserted inside the worker)."""
    _run_two_process_vs_single("pp")
