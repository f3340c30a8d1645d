"""The source framework's own count, kept so that BASELINE.md's A100 rows stay
comparable: 6 N + 12 L s h with N every parameter (embedding rows included) and
attention uncausal. It overstates what the chip has to do."""


def count(shape, run: dict) -> dict:
    return {"ops_per_token": 6 * shape.all_params() + 12 * shape.n_layer * run["sequence_length"] * shape.n_embd}
