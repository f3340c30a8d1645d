"""Process/runtime environment (reference: src/modalities/running_env/cuda_env.py:15-67).

CudaEnv's job (init_process_group("nccl"), set_device, teardown) maps to:
``jax.distributed.initialize()`` on multi-host TPU pods (single-host needs nothing),
OOM-aware error logging on exit, and no explicit device selection (the runtime owns
placement). The context-manager shape is preserved so orchestration code reads the
same.
"""

from __future__ import annotations

import os
import traceback
from pathlib import Path
from typing import Optional

from modalities_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# The path is part of a cache entry's key, so a directory that moves with the user,
# the process or the clock never hits: one fixed place inside the checkout.
COMPILATION_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_compilation_cache"


def configure_compilation_cache() -> str:
    """Where this process keeps JAX's persistent compilation cache — the one seam
    `run`, `warmstart`, `serve` and `chip_smoke.py` all pass. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own setting stands and nothing is
    set here; otherwise ``COMPILATION_CACHE_DIR``.

    Wherever the cache is, an entry's key takes in the program's metadata. JAX leaves
    it out by default, and two trees whose programs differ only in metadata (a scope
    added or renamed: `telemetry/scopes.py`) then share one entry: the second gets the
    first's executable back, with the first's `op_name`s, and a profile of it names
    every operation by scopes its own source no longer has. The price is that a tree
    whose traced lines moved compiles once more (the metadata holds file names and
    line numbers): a cold compile where an entry of another tree would have been hit."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(COMPILATION_CACHE_DIR))
    return str(COMPILATION_CACHE_DIR)


class TpuEnv:
    """Context manager for the distributed runtime (CudaEnv equivalent). Also
    places the compilation cache (`configure_compilation_cache`): first compiles of
    a large train step take a quarter of a minute and more, and restarts and
    warmstarts then reuse the compiled program."""

    def __init__(self, process_group_backend: Optional[str] = None, timeout_s: int = 600):
        # backend arg accepted for config parity; collectives are XLA's
        self.process_group_backend = process_group_backend
        self.timeout_s = timeout_s
        self._initialized_distributed = False

    def __enter__(self) -> "TpuEnv":
        import jax

        from modalities_tpu.telemetry import span

        configure_compilation_cache()

        # the backend's own start (the coordinator's handshake, the first device list):
        # no `Telemetry` is active yet, so the span goes to the process log and the
        # run's instance takes it over when `Main.run` activates it
        with span("backend_start"):
            coordinator = os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get("COORDINATOR_ADDRESS")
            num_processes = os.environ.get("JAX_NUM_PROCESSES") or os.environ.get("NNODES")
            if coordinator and num_processes and int(num_processes) > 1:
                jax.distributed.initialize(
                    coordinator_address=coordinator,
                    num_processes=int(num_processes),
                    process_id=int(os.environ.get("JAX_PROCESS_ID", os.environ.get("RANK", 0))),
                    initialization_timeout=self.timeout_s,
                )
                self._initialized_distributed = True
            logger.info(
                "TpuEnv: %d devices over %d processes (platform=%s)",
                len(jax.devices()),
                jax.process_count(),
                jax.devices()[0].platform,
            )
        return self

    def __exit__(self, exc_type, exc_val, exc_tb) -> bool:
        if exc_type is not None:
            message = "".join(traceback.format_exception(exc_type, exc_val, exc_tb))
            if "RESOURCE_EXHAUSTED" in message or "Out of memory" in message:
                logger.error("Device out of memory:\n%s", message)
            else:
                logger.error("Error in TpuEnv context:\n%s", message)
        if self._initialized_distributed:
            import jax

            jax.distributed.shutdown()
        return False


# alias kept so reference-style code reads unchanged
CudaEnv = TpuEnv
