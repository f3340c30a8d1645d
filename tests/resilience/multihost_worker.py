"""Worker for the 2-process resilience tests (run via subprocess, not pytest).

Modes (after `jax.distributed.initialize` over 2 CPU processes):

- ``heartbeat``: start the KV-store HeartbeatMonitor on both ranks; rank 1 dies
  abruptly through the `peer_death` fault point (os._exit(1), no leaving beat)
  while rank 0's main thread sleeps as if stuck in a collective. The monitor
  thread on rank 0 must convert the silence into a diagnosed RESUMABLE_EXIT_CODE
  exit with a peer-failure artifact — no XLA collectives involved, so this mode
  runs on every jaxlib.
- ``consensus``: drive the full config-driven app (Main -> Gym -> Trainer) with
  `stop_consensus: "on"` and `sigterm_one_rank@5:0` armed via the environment on
  BOTH ranks: only rank 0 receives the signal, the vote rides the step-6 ballot,
  and the one-step-lagged decision stops BOTH ranks at step 7. Requires
  cross-process CPU collectives (the parent probe-gates it).

Usage: multihost_worker.py <coordinator_port> <process_id> <num_processes> <mode>
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_n_dev = os.environ.get("MP_WORKER_DEVICES", "4")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={_n_dev}"
).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def heartbeat_run(rank: int) -> None:
    import time
    from pathlib import Path

    from modalities_tpu.resilience import faults
    from modalities_tpu.resilience.heartbeat import HeartbeatMonitor, KVStoreTransport

    monitor = HeartbeatMonitor(
        rank=rank,
        world=2,
        transport=KVStoreTransport(),
        interval_s=0.2,
        peer_deadline_s=2.5,
        artifact_dir=Path(os.environ["MP_ARTIFACT_DIR"]),
    )
    monitor.start()
    print("HB STARTED", flush=True)
    time.sleep(1.0)  # both sides exchange a few beats first
    if rank == 1:
        faults.arm_faults("peer_death@0")
        faults.peer_death_if_armed(0)  # os._exit(1): abrupt, no leaving beat
    # rank 0's main thread is "stuck in a collective" — only the monitor thread
    # can end this process, via os._exit(RESUMABLE_EXIT_CODE)
    time.sleep(60.0)
    print("SURVIVOR NEVER EXITED", flush=True)
    sys.exit(3)


def consensus_run() -> None:
    from pathlib import Path

    from modalities_tpu.main import Main
    from modalities_tpu.resilience import PreemptionShutdown

    main = Main(
        Path(os.environ["MP_CONSENSUS_CONFIG"]),
        experiments_root_path=Path("data") / "experiments",
        experiment_id="mp_consensus",
    )
    try:
        main.run(main.build_components())
    except PreemptionShutdown as e:
        print(f"STOPPED {e}", flush=True)
        sys.exit(75)
    print("NO STOP", flush=True)
    sys.exit(4)


def main() -> None:
    port, pid, nprocs = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    mode = sys.argv[4]
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}", num_processes=nprocs, process_id=pid
    )
    if mode == "heartbeat":
        heartbeat_run(pid)
    elif mode == "consensus":
        consensus_run()
    else:
        raise SystemExit(f"unknown mode {mode!r}")


if __name__ == "__main__":
    main()
