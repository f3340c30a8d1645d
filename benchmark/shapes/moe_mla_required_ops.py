"""Operations one trained token requires of the MXU in the latent-attention / expert-layer
decoder: 6 x the parameters it multiplies (forward 2, backward 4): all of latent attention's
four projections in every layer; the dense layers' SwiGLU; in an expert layer the router, the
shared expert and as many routed experts as the pairs a token brought to HELD experts, as the
program's own counter read them in the window (`run["pairs_held_per_token"]`: about 0.75 where
16 of 128 experts are held and 6 chosen, not the 6 a whole model computes and never the 16 a
dense pass over the held experts would); the untied head (the embedding is a gather). Plus the
causal scores, 3 H (d_qk + d_v) S a layer: Q K^T at 192 and P V at 128, forward and twice
that backward, the causal half. Nothing recomputed is counted, although the configuration
rematerializes every block."""


def count(shape, run: dict) -> dict:
    passed = sum(shape.layer_matmul_params_passed(kind, run["pairs_held_per_token"]) for kind in shape.kinds)
    scores = 3 * shape.n_head * (shape.qk_head_dim + shape.v_head_dim) * run["sequence_length"] * shape.n_layer
    return {"ops_per_token": 6 * (passed + shape.n_embd * shape.vocab_size) + scores}
