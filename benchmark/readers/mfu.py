"""Model-FLOP utilisation: operations per token (from the shape function named in
`shape_function`) x tokens per second of the window, over chips x the bf16 peak."""


def read(spec: dict, observed: dict, trace, env: dict):
    rate = observed.get("tokens_per_s")
    if rate is None:
        return None
    per_token = env["shape_function"](spec["shape_function"])(env["shape"], env["run"])["ops_per_token"]
    return 100.0 * per_token * rate / (env["chips"] * env["peaks"]["bf16_flops"])
