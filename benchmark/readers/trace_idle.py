"""Idle share of the traced window: 1 - union of the device's operation intervals over
the window, averaged over the chips."""

from benchmark import xtrace


def read(spec: dict, observed: dict, trace, env: dict):
    return None if trace is None or not trace.devices else 100.0 * xtrace.idle_share(trace)
