"""Required operations and bytes of one call of each flash-attention kernel in the attention layer of
the Mamba-2 cell (PR 52), on the rows and heads one chip holds: the labels this cell's trace holds
(`flash_attention_fwd` and, by what `flash_tile_plan` names, the fused `flash_attention_bwd` or
`flash_attention_bwd_dq` + `_bwd_dkv`) at 8 query heads on 2 key/value heads of 128 and one row of
8,192, counted by the score entries a causal query may see, S (S + 1) / 2 a head, and never by tiles.
One matmul over n entries at width D costs u = 2 B H n D operations. The mathematics needs 2 in the
forward pass (Q K^T, P V) and 4 in the backward (dV, dP, dQ, dK); the scores the backward computes
again are not required work, whether one kernel does it or two (split as
`benchmark/shapes/flash_attention.py` splits them). What a tile computes above the diagonal is not
required either, so the share shows what the tiles waste and cannot pass 100. Bytes: q, k, v (and o
and do in the backward) read once, the results written once, bfloat16. The scores' scale (1/128, not
1/sqrt(128)) is a constant inside the kernels and costs nothing."""


def count(shape, run: dict) -> dict:
    b, hq, hkv = run["rows_per_chip"], run["q_heads_per_chip"], run["kv_heads_per_chip"]
    s, d = run["sequence_length"], shape.attn_head_dim
    u = 2.0 * b * hq * (s * (s + 1) // 2) * d
    q_bytes, kv_bytes = 2 * b * hq * s * d, 2 * b * hkv * s * d
    return {
        "flash_attention_fwd": {"ops": 2.0 * u, "bytes": 2 * q_bytes + 2 * kv_bytes},
        "flash_attention_bwd": {"ops": 4.0 * u, "bytes": 4 * q_bytes + 4 * kv_bytes},  # q o do dq | k v dk dv
        "flash_attention_bwd_dq": {"ops": 1.5 * u, "bytes": 4 * q_bytes + 2 * kv_bytes},
        "flash_attention_bwd_dkv": {"ops": 2.5 * u, "bytes": 3 * q_bytes + 4 * kv_bytes},
    }
