"""Operations one trained token requires of the MXU in the window-and-global attention /
expert-layer decoder: 6 x the parameters it multiplies (forward 2, backward 4): attention's
four projections (q is n_head_q x head_dim wide, whatever n_embd / n_head_q is) and the
router in every layer, as many routed experts as the pairs a token brought to HELD experts,
as the program's own counter read them in the window (`run["pairs_held_per_token"]`: about 1.0
where 8 of 64 experts are held and 8 chosen, not the 8 a whole model computes), and the untied
head (the embedding is a gather). Plus attention's two products over the positions a token
may see, forward and twice that backward: 12 Hq D p a layer, with p the mean over the row of
the positions a query sees, (S + 1) / 2 on a global layer and about W on a window layer
(`SwaMoEShape.positions_seen`: the window's, not the tiles'). Nothing recomputed is counted,
although the configuration rematerializes every block."""


def count(shape, run: dict) -> dict:
    passed = shape.n_layer * shape.layer_matmul_params_passed(run["pairs_held_per_token"])
    seen = sum(shape.positions_seen(kind, run["sequence_length"]) for kind in shape.kinds)
    return {"ops_per_token": 6 * (passed + shape.n_embd * shape.vocab_size) + 12 * shape.n_head_q * shape.head_dim * seen}
