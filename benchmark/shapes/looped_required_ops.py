"""Operations one trained token requires of the MXU in the looped decoder: a parameter is counted
once for every application, so 6 x a layer's seven kernels (forward 2, backward 4) x L layers x T
walks; causal attention 6 s h a layer application (the 12 s h of full attention, of which the causal
mask needs half); and the untied head once for every exit, 6 x E x V x T (the embedding is a gather;
the gate's vector, E a token an exit, is left out). Nothing recomputed is counted, although the
configuration rematerializes every block."""


def count(shape, run: dict) -> dict:
    applications = shape.n_layer * shape.total_ut_steps
    layers = 6 * shape.layer_matmul_params() * applications
    attention = 6 * applications * run["sequence_length"] * shape.n_embd
    head = 6 * shape.total_ut_steps * shape.n_embd * shape.vocab_size
    return {"ops_per_token": layers + attention + head}
