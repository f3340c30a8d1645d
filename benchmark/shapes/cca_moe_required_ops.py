"""Operations one trained token requires of the MXU in the compressed-convolutional-attention /
expert-layer decoder: 6 x the parameters it multiplies (forward 2, backward 4): in every
layer the four projections into the latent and the one out of it, the grouped convolution's
two `[d, d]` products a latent head, the router's four matrices (down, two hidden layers, the
columns), and as many held experts as the pairs a token brought to them, as the program's own
counter read them in the window (`run["pairs_held_per_token"]`: 8/17 at balance where 8 of 16
experts are held, one of 17 columns chosen and the skip column passes none); and the tied
head (the embedding is a gather). Plus attention's two products over the positions a token
may see, forward and twice that backward: 12 Hq d p a layer, p = (S + 1) / 2, the mean over a
row of the positions a causal query sees. The depthwise convolution, the norms, the merges
and the rotary are elementwise and count nothing. Nothing recomputed is counted, although
the configuration rematerializes every block."""


def count(shape, run: dict) -> dict:
    passed = shape.n_layer * shape.layer_matmul_params_passed(run["pairs_held_per_token"])
    seen = shape.n_layer * (run["sequence_length"] + 1) / 2
    return {"ops_per_token": 6 * (passed + shape.n_embd * shape.vocab_size) + 12 * shape.n_head_q * shape.head_dim * seen}
