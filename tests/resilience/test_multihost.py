"""2-process resilience e2e: the cluster-coordination acceptance scenarios with
real separate processes under jax.distributed.

(i)  peer death -> heartbeat deadline -> the SURVIVOR exits resumable with a
     diagnosed peer-failure artifact. Pure KV-store traffic (no XLA
     collectives), so this tier runs on every jaxlib.
(ii) staggered preemption (`sigterm_one_rank`) -> stop-flag consensus -> BOTH
     ranks exit at the same step boundary behind one forced checkpoint. Needs
     cross-process CPU collectives, so it probe-skips on jaxlibs without them
     (same gate as tests/parallel/test_multiprocess.py).
"""

import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from modalities_tpu.resilience import RESUMABLE_EXIT_CODE
from modalities_tpu.resilience.manifest import MANIFEST_FILE_NAME
from tests.conftest import CPU_COMPILE_FLAGS

WORKER = Path(__file__).parent / "multihost_worker.py"
CONFIG = Path(__file__).parent.parent.parent / "configs" / "config_lorem_ipsum_tpu.yaml"

_MP_CPU_UNSUPPORTED = "Multiprocess computations aren't implemented on the CPU backend"


def _clean_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = CPU_COMPILE_FLAGS  # the worker adds its own device count (4 per process)
    env.pop("MODALITIES_TPU_FAULTS", None)
    env["PYTHONPATH"] = str(WORKER.parent.parent.parent)
    return env


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _require_mp_cpu_collectives() -> None:
    # Reuse tests/parallel's session-memoized probe: one probe pair per pytest
    # process, no matter how many 2-process tiers gate on it.
    from tests.parallel import test_multiprocess as _mp

    _mp._require_mp_cpu_collectives()


def _spawn_pair(mode: str, env: dict, cwd=None):
    port = _free_port()
    return [
        subprocess.Popen(
            [sys.executable, str(WORKER), str(port), str(pid), "2", mode],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env, cwd=cwd,
        )
        for pid in range(2)
    ]


# --------------------------------------------------- (i) peer death -> exit 75


def test_peer_death_turns_survivor_hang_into_resumable_exit(tmp_path):
    """Rank 1 dies abruptly (peer_death fault: os._exit(1), no leaving beat)
    while rank 0's main thread is wedged. Rank 0's heartbeat monitor must
    detect the silence within its deadline and exit RESUMABLE_EXIT_CODE with a
    peer-failure artifact naming the dead rank — instead of hanging forever."""
    env = {**_clean_env(), "MP_ARTIFACT_DIR": str(tmp_path)}
    procs = _spawn_pair("heartbeat", env)
    results = []
    for p in procs:
        out, err = p.communicate(timeout=120)
        results.append((p.returncode, out, err))

    # both monitors came up and exchanged beats before the fault fired
    assert all("HB STARTED" in out for _, out, _ in results), results
    # rank 1: the injected abrupt death
    assert results[1][0] == 1, results[1][2][-3000:]
    # rank 0: NOT the 60s wedge — the monitor converted silence into EX_TEMPFAIL
    assert results[0][0] == RESUMABLE_EXIT_CODE, results[0][2][-3000:]
    assert "SURVIVOR NEVER EXITED" not in results[0][1]

    dump_path = tmp_path / "watchdog_dump_rank_0_peer_peer_dead.json"
    assert dump_path.is_file()
    dump = json.loads(dump_path.read_text())
    assert dump["event"] == "peer_failure"
    assert dump["detail"]["dead_ranks"] == [1]
    assert dump["state"]["process_count"] == 2
    assert dump["thread_stacks"]  # diagnosable: what rank 0 was stuck in


# ------------------------------------- (ii) staggered SIGTERM -> consensus stop


def test_sigterm_one_rank_stops_both_ranks_at_the_same_step(tmp_path):
    """The tentpole scenario end-to-end: SIGTERM on ONE rank only. Without the
    ballot, rank 0 would checkpoint-and-exit while rank 1 blocks forever in the
    next collective; with `stop_consensus: "on"` both ranks agree through the
    in-step all-reduce and exit resumable at the SAME step (7 = signal at 5 +
    vote at 6 + one-step-lagged decision), behind ONE forced checkpoint."""
    _require_mp_cpu_collectives()

    from modalities_tpu.dataloader.packed_data import write_pbin_file

    rng = np.random.default_rng(0)
    (tmp_path / "data").mkdir()
    tokens = rng.integers(0, 256, size=56000)
    write_pbin_file(tmp_path / "data" / "lorem_ipsum.pbin", iter([tokens]), token_size_in_bytes=2)

    config_text = (
        CONFIG.read_text()
        .replace("num_target_tokens: 32768", "num_target_tokens: 49152")
        .replace("num_target_steps: 8", "num_target_steps: 12")
        .replace("    anomaly_policy: raise", '    anomaly_policy: raise\n    stop_consensus: "on"')
    )
    config = tmp_path / "config_mp_consensus.yaml"
    config.write_text(config_text)

    env = {
        **_clean_env(),
        "MP_CONSENSUS_CONFIG": str(config),
        "MODALITIES_TPU_FAULTS": "sigterm_one_rank@5:0",  # both arm it; only rank 0 fires
    }
    procs = _spawn_pair("consensus", env, cwd=tmp_path)
    results = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        if _MP_CPU_UNSUPPORTED in err:
            pytest.skip(f"jaxlib: {_MP_CPU_UNSUPPORTED}")
        results.append((p.returncode, out, err))

    # BOTH ranks exited resumable at the same agreed boundary
    for code, out, err in results:
        assert code == RESUMABLE_EXIT_CODE, err[-3000:]
        assert "step 7" in out, out

    # one forced out-of-schedule checkpoint, sealed for warmstart
    ring = tmp_path / "data" / "checkpoints"
    forced = [p for p in ring.glob("eid_mp_consensus-*") if "seen_steps_7-" in p.name]
    assert len(forced) == 1
    assert (forced[0] / MANIFEST_FILE_NAME).is_file()
