"""A number the harness or the program counted: `key` of what the run observed, reduced
(`reduce`: median of a list), divided by another (`over`), times `scale`."""

from benchmark.stats import median


def read(spec: dict, observed: dict, trace, env: dict):
    value = observed.get(spec["key"])
    if value is None:
        return None
    if spec.get("reduce") == "median":
        if not value:
            return None
        value = median(value)
    if "over" in spec:
        below = observed.get(spec["over"])
        if not below:
            return None
        value = value / below
    return float(value) * float(spec.get("scale", 1.0))
