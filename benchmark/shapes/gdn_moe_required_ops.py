"""Operations one trained token requires of the MXU in the gated-delta-rule / gated-attention /
expert-layer decoder: 6 x the parameters it multiplies (forward 2, backward 4): in a rule layer the
mixer's three projections (`qkvz`, `ba`, the projection back) and the convolution's taps (a
multiply-add a tap and a channel); in the attention layer its four projections, q twice as wide for
the gate; in every layer the router over all 512 experts, the shared expert and its gate, and as
many routed experts as the pairs a token brought to HELD experts, as the program's own counter read
them in the window (`run["pairs_held_per_token"]`: about 1.25 where 64 of 512 are held and 10 chosen,
not the 10 a whole model computes); and the untied head (the embedding is a gather). Plus 3 x the
chunked rule's own forward products a rule layer (`GdnMoEShape.rule_forward_ops_per_token`: a chunk's
`k k^T`, `q k^T`, `T` against its two right sides, the three products with the state and the lower
product; the series that inverts `I + L` is this program's way and is not counted), and the gated
attention's two products over the positions a token may see, forward and twice that backward:
12 Hq D (S + 1) / 2. Nothing recomputed is counted, although the configuration rematerializes
every block and the rule every group of chunks inside it."""


def count(shape, run: dict) -> dict:
    seq = run["sequence_length"]
    every_layer = shape.outside_experts_params() + run["pairs_held_per_token"] * shape.expert_params()
    passed = sum((shape.gdn_matmul_params() + shape.taps * shape.conv_width if kind == "gdn" else shape.attention_matmul_params()) + every_layer
                 for kind in shape.kinds)
    rule = 3 * shape.kinds.count("gdn") * shape.rule_forward_ops_per_token()
    scores = 12 * shape.n_head_q * shape.head_dim * shape.kinds.count("attn") * (seq + 1) / 2
    return {"ops_per_token": 6 * (passed + shape.n_embd * shape.vocab_size) + rule + scores}
