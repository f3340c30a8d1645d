"""A kernel's share of its roofline: the least time the chip could take for the calls
the trace holds — per call the larger of required operations over the bf16 peak and
required bytes over the memory bandwidth, from the shape function named in
`shape_function` — over the device time those calls took. `pattern` selects the
kernel's operations by label; the shape function returns {label: {"ops", "bytes"}} per call."""

from benchmark import xtrace


def read(spec: dict, observed: dict, trace, env: dict):
    if trace is None:
        return None
    per_call = env["shape_function"](spec["shape_function"])(env["shape"], env["run"])
    least = took = 0.0
    for device in xtrace.label_events(trace, spec["pattern"]):
        for event in device:
            need = per_call.get(xtrace.op_label(event))
            if need is None:
                raise SystemExit(f"benchmark: shape function {spec['shape_function']!r} knows no kernel "
                                 f"{xtrace.op_label(event)!r} (it knows {sorted(per_call)})")
            least += max(need["ops"] / env["peaks"]["bf16_flops"], need["bytes"] / env["peaks"]["hbm_bytes_per_s"])
            took += event.seconds
    return 100.0 * least / took if took else None
