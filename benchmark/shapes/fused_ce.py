"""Required operations and bytes of one call of each fused cross-entropy kernel, on the
rows and vocabulary columns one chip holds: each of the three does one required matmul
of N x E x V (the logits; dh = dlogits W; dW = dlogits^T h). The logits that both
backward kernels recompute are not required work. Bytes: hidden rows and head matrix
read once, the result written once, bfloat16 (the head's gradient in float32)."""


def count(shape, run: dict) -> dict:
    n, e, v = run["ce_rows_per_chip"], shape.n_embd, run["vocab_per_chip"]
    matmul = 2.0 * n * e * v
    read = 2 * n * e + 2 * e * v
    return {
        "fused_ce_fwd": {"ops": matmul, "bytes": read + 8 * n},
        "fused_ce_bwd_dh": {"ops": matmul, "bytes": read + 2 * n * e},
        "fused_ce_bwd_dw": {"ops": matmul, "bytes": read + 4 * e * v},
    }
