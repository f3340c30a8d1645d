"""Operations one trained token requires of the MXU in the attention / state-space hybrid
decoder: 6 x the parameters that take part in matrix multiplications (forward 2, backward
4; the tied table counts once, as the head: its use as the embedding is a gather), plus
causal attention, 6 s h for each ATTENTION layer (the 12 s h of full attention, of which
the causal mask needs half). Nothing recomputed is counted, although the configuration
rematerializes every block. The selective scan's work is elementwise (about 9 d_inner
d_state operations a token a layer and pass, on the vector unit): it is not MXU work, has
no share in this peak and is left out, so this share says how much of the step the
matmuls could fill, not how busy the chip is."""


def count(shape, run: dict) -> dict:
    attention = 6 * shape.kinds.count("attn") * run["sequence_length"] * shape.n_embd
    return {"ops_per_token": 6 * shape.matmul_params() + attention}
