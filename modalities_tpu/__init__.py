"""modalities-tpu: a TPU-native (JAX/XLA/Pallas) framework for distributed LLM training.

Re-imagines the capabilities of the reference `modalities` framework
(PyTorch/CUDA/NCCL) on top of JAX: GSPMD sharding over a named device mesh
replaces FSDP/DTensor/pipelining wrappers, one jitted ``train_step`` replaces
the eager micro-batch loop internals, Orbax replaces torch DCP, and Pallas
kernels replace flash-attn CUDA kernels.

The YAML config + registry + component-factory dependency-injection system is
preserved as the user-facing API (reference: src/modalities/config/component_factory.py,
src/modalities/registry/components.py).
"""

import time as _time

# The origin of the process's own timeline (telemetry/spans.PROCESS_LOG): the first line
# the package runs, on the clock every span, compile stamp and goodput ledger uses.
IMPORTED_AT = _time.perf_counter()

__version__ = "0.1.0"
