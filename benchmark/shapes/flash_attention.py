"""Required operations and bytes of one call of each flash-attention kernel, on the
rows and heads one chip holds.

One causal matmul over a [S, S] score matrix costs u = 2 B H S S D / 2 operations.
The mathematics needs 2 in the forward pass (Q K^T, P V) and 4 in the backward
(dV, dP, dQ, dK); recomputing the scores, which both backward kernels do, is not
required work. The backward's 4 are split over its two kernels as what each produces:
dQ and half of dP to `bwd_dq`, dV, dK and the other half to `bwd_dkv`.
Bytes: each kernel reads q, k, v (and o/do in the backward) once and writes its
results once, in bfloat16."""


def count(shape, run: dict) -> dict:
    b, hq, hkv = run["rows_per_chip"], run["q_heads_per_chip"], run["kv_heads_per_chip"]
    s, d = run["sequence_length"], shape.head_dim
    u = b * hq * s * s * d
    q_bytes, kv_bytes = 2 * b * hq * s * d, 2 * b * hkv * s * d
    return {
        "flash_attention_fwd": {"ops": 2.0 * u, "bytes": 2 * q_bytes + 2 * kv_bytes},
        "flash_attention_bwd_dq": {"ops": 1.5 * u, "bytes": 4 * q_bytes + 2 * kv_bytes},
        "flash_attention_bwd_dkv": {"ops": 2.5 * u, "bytes": 3 * q_bytes + 4 * kv_bytes},
    }
