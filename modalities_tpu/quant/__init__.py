"""Quantized inference subsystem (ISSUE 14, ROADMAP item 5b).

Three pillars; the kernel among them runs by ops/tiers.py's one rule (on a TPU):

- `core`    — symmetric per-channel / per-block int8 and fp8 quantize/dequantize
              primitives with explicit scale layouts (the numerics ground truth).
- `weights` — weight-only serving: params are quantized ONCE at load time through
              the shared `load_serving_params` seam, dequantized on the fly in the
              matmul path (Pallas fused dequant-matmul, ops/quant_matmul.py).
- `kv`      — int8 paged KV pool helpers: mode resolution and the host-side
              scale-allocation mirror the pool fuzz audits.

Quantized modes are excluded from the bitwise interactive-parity pins; `oracle`
gates them instead (max-abs logit error + greedy token-match rate vs bf16).
"""

from modalities_tpu.quant.core import (  # noqa: F401
    dequantize,
    quantize_fp8,
    quantize_per_block,
    quantize_per_channel,
)
from modalities_tpu.quant.weights import (  # noqa: F401
    infer_quant_mode,
    quant_storage_dtype,
    quantize_params,
    quantized_model,
    resolve_quant_weights_mode,
    weights_bytes_saved,
)
from modalities_tpu.quant.oracle import OracleReport, run_oracle  # noqa: F401
from modalities_tpu.quant.kv import (  # noqa: F401
    KVScaleMirror,
    resolve_quant_kv_mode,
)
