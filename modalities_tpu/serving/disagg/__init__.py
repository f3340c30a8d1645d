"""Disaggregated prefill/decode serving (PR 18; ROADMAP item 2 rung b).

One serving fleet splits into a PREFILL tier (engines in `role="prefill"`:
chunked prefill to completion, first token sampled on-device, decode path
never built) and a DECODE tier (engines in `role="decode"`: block import +
the shared decode executable only). The seam between them is the versioned
KV handoff record (handoff.py): pool-layout block payloads (int8 blocks +
their f32 scale mirror under `quant_kv: int8`, bf16 otherwise), the
position-ordered block table, sampler state, last token, and a payload
digest. The record changes WHERE work runs, never the tokens — greedy
disaggregated output is bitwise equal to the combined paged path.

- handoff.py   — HandoffRecord + digest + wire (JSON) serialization
- pair.py      — in-process 1-prefill + 1-decode harness (the tests' oracles)
- router.py    — DisaggRouter: two-leg dispatch (prefill leg -> handoff ->
                 decode leg) streaming ONE SSE answer, X-Trace-Id across
                 both legs, decode-leg failover via a fresh prefill
- component.py — config/DI surface (`inference_component` variant "disagg")
"""

from modalities_tpu.serving.disagg.handoff import (  # noqa: F401
    HANDOFF_VERSION,
    HandoffRecord,
    HandoffRejected,
)
