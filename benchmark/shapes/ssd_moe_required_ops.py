"""Operations one trained token requires of the MXU in the Mamba-2 / NoPE-attention / expert-layer
decoder: 6 x the parameters it multiplies (forward 2, backward 4): in a Mamba-2 layer the mixer's two
projections as held (`in_proj` 4096 x 4384, `out_proj` 2048 x 4096) and the convolution's taps (a
multiply-add a tap and a channel); in the attention layer its four projections as held; in every layer
the router over all 72 experts, the shared expert's slice, and as many routed experts as the pairs a
token brought to HELD experts, as the program's own counter read them in the window
(`run["pairs_held_per_token"]`: about 1.25 where 9 of 72 are held and 10 chosen, not the 10 a whole
model computes); and the tied head (the embedding is a gather). Plus 3 x the chunked form's own forward
products a Mamba-2 layer (`SsdMoEShape.scan_forward_ops_per_token`: a chunk's `C B^T` once, and a held
head's `(L o C B^T) X`, its own state `X^T B` and `C H`), and the attention's two products over the
positions a token may see, forward and twice that backward: 12 Hq D (S + 1) / 2. Nothing recomputed is
counted, although the configuration rematerializes every block."""


def count(shape, run: dict) -> dict:
    seq = run["sequence_length"]
    every_layer = shape.outside_experts_params() + run["pairs_held_per_token"] * shape.expert_params()
    passed = sum((shape.ssd_matmul_params() + shape.taps * shape.conv_width if kind == "ssd" else shape.attention_params()) + every_layer
                 for kind in shape.kinds)
    scan = 3 * shape.kinds.count("ssd") * shape.scan_forward_ops_per_token()
    scores = 12 * shape.n_head_q * shape.attn_head_dim * shape.kinds.count("attn") * (seq + 1) / 2
    return {"ops_per_token": 6 * (passed + shape.n_embd * shape.vocab_size) + scan + scores}
