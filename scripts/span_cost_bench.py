"""What a host span costs (telemetry/spans.py): per `with span(...)` in microseconds, with the profiler
off and on, and the share of it that is the process log's (`PROCESS_LOG.add`, timed alone).

    python3 scripts/span_cost_bench.py [--trace <dir>] [--spans 20000]

Host code only: the device is not touched but for the profiler's own start. Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def per_call_us(fn, n: int) -> float:
    fn()  # warm
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return 1e6 * (time.perf_counter() - t0) / n


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--trace", default=None, help="where the profiler writes while it is on (default: a temporary directory)")
    parser.add_argument("--spans", type=int, default=20000)
    args = parser.parse_args()

    import jax

    from modalities_tpu.telemetry import Telemetry, span
    from modalities_tpu.telemetry.spans import PROCESS_LOG, SpanRecord

    def one(opener):
        def call():
            with opener("cost"):
                pass
        return call

    instance = Telemetry(watchdog_deadline_s=0)
    record = SpanRecord(name="cost", ts=0.0, dur_s=0.0, self_s=0.0, thread="MainThread", timeline=True)
    out = {
        "device": jax.devices()[0].device_kind,
        "disabled_instance_us": per_call_us(one(Telemetry(enabled=False).span), args.spans),
        "instance_us": per_call_us(one(instance.span), args.spans),
        "no_instance_active_us": per_call_us(one(span), args.spans),
        "log_add_alone_us": per_call_us(lambda: PROCESS_LOG.add(record, True), args.spans),
        "record_made_alone_us": per_call_us(
            lambda: SpanRecord(name="cost", ts=0.0, dur_s=0.0, self_s=0.0, thread="MainThread", timeline=True, t0=0.0, parent=None, step=1),
            args.spans),
    }
    with tempfile.TemporaryDirectory() as scratch:
        jax.profiler.start_trace(args.trace or scratch)
        try:
            out["instance_profiler_on_us"] = per_call_us(one(instance.span), args.spans)
            out["no_instance_active_profiler_on_us"] = per_call_us(one(span), args.spans)
        finally:
            jax.profiler.stop_trace()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
