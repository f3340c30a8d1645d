"""Operations one trained token requires in the dense decoder: 6 x the parameters that
take part in matrix multiplications (forward 2, backward 4; embedding rows are a gather
and do not count), plus causal attention, 6 L s h (the 12 L s h of full attention, of
which the causal mask needs half). Nothing recomputed is counted."""


def count(shape, run: dict) -> dict:
    attention = 6 * shape.n_layer * run["sequence_length"] * shape.n_embd
    return {"ops_per_token": 6 * shape.matmul_params() + attention}
