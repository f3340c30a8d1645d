"""Required operations and bytes of one call of each flash-attention kernel of a stack of window
and global attention layers (PR 38), on the rows and heads one chip holds, counted by the
score entries a query may see and never by tiles.

A global layer's call (labels `flash_attention_fwd`, `flash_attention_bwd`, and the two-kernel
backward's `flash_attention_bwd_dq` / `_bwd_dkv` where a row's dq does not fit VMEM) sees the causal
triangle, S (S + 1) / 2 entries a head. A window layer's call (`flash_attention_window_*`)
sees the window's band: position i sees itself and the W - 1 before it, W (W + 1) / 2 +
(S - W) W entries a head (16,253,440 at S 16,384, W 1024). One matmul over n entries at
width D costs u = 2 B H n D operations. The mathematics needs 2 in the forward pass (Q K^T,
P V) and 4 in the backward (dV, dP, dQ, dK); the scores the backward computes again are not
required work, whether one kernel does it or two (split as `benchmark/shapes/flash_attention.py`
splits them). What a tile computes outside the band or above the diagonal is not required
either, so the share shows what the tiles waste and cannot pass 100. Bytes: q, k, v (and o
and do in the backward) read once, the results written once, bfloat16."""


def entries(seq: int, window: int | None) -> int:
    """Score entries one head's queries may see: the causal triangle, or the window's band inside it."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def count(shape, run: dict) -> dict:
    b, hq, hkv = run["rows_per_chip"], run["q_heads_per_chip"], run["kv_heads_per_chip"]
    s, d = run["sequence_length"], shape.head_dim
    q_bytes, kv_bytes = 2 * b * hq * s * d, 2 * b * hkv * s * d
    out = {}
    for prefix, window in (("flash_attention_", None), ("flash_attention_window_", shape.sliding_window)):
        u = 2.0 * b * hq * entries(s, window) * d
        out[prefix + "fwd"] = {"ops": 2.0 * u, "bytes": 2 * q_bytes + 2 * kv_bytes}
        out[prefix + "bwd"] = {"ops": 4.0 * u, "bytes": 4 * q_bytes + 4 * kv_bytes}  # q o do dq | k v dk dv
        out[prefix + "bwd_dq"] = {"ops": 1.5 * u, "bytes": 4 * q_bytes + 2 * kv_bytes}
        out[prefix + "bwd_dkv"] = {"ops": 2.5 * u, "bytes": 3 * q_bytes + 4 * kv_bytes}
    return out
