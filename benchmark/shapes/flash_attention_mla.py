"""Required operations and bytes of one call of each flash-attention kernel where q and k
are wider than v (latent attention: 192 and 128), on the rows and heads one chip holds.

One causal matmul over a [S, S] score matrix at width D costs u(D) = 2 B H S S D / 2
operations. The forward needs Q K^T at the width of q and k and P V at the width of v; the
backward dQ and dK at the first, dV and dP at the second. Recomputing the scores, which both
backward kernels do, is not required work. The backward's four are split over its two
kernels as `benchmark/shapes/flash_attention.py` splits them: dQ and half of dP to
`bwd_dq`, dV, dK and the other half to `bwd_dkv`. Bytes by the real widths, bfloat16: each
kernel reads q, k, v (and o and do in the backward) once and writes its results once."""


def count(shape, run: dict) -> dict:
    b, h, s = run["rows_per_chip"], run["q_heads_per_chip"], run["sequence_length"]
    wide, narrow = shape.qk_head_dim, shape.v_head_dim
    u_wide, u_narrow = b * h * s * s * wide, b * h * s * s * narrow
    bytes_wide, bytes_narrow = 2 * b * h * s * wide, 2 * b * h * s * narrow  # one array of q's width, of v's
    return {
        "flash_attention_fwd": {"ops": u_wide + u_narrow, "bytes": 2 * bytes_wide + 2 * bytes_narrow},  # q k | v o
        "flash_attention_bwd_dq": {"ops": u_wide + 0.5 * u_narrow, "bytes": 3 * bytes_wide + 3 * bytes_narrow},  # q k dq | v o do
        "flash_attention_bwd_dkv": {"ops": u_wide + 1.5 * u_narrow, "bytes": 3 * bytes_wide + 4 * bytes_narrow},  # q k dk | v o do dv
    }
