"""Shared retry-with-exponential-backoff-and-jitter for checkpoint IO.

Transient storage errors (flaky NFS/GCS mounts on preemptible pods) should cost
a retry, not the run. Every attempt after the first runs under a
``ckpt_retry/<what>`` telemetry span (goodput bucket: recovery) and emits a
``ckpt_retry/attempt`` event, so a run that survived on retries is visible in
the sink and in the in-process event counts (`resilience/events.py`).

Defaults are env-tunable so chaos tests stay fast without plumbing config
through the checkpoint layers:
- ``MODALITIES_TPU_IO_RETRY_ATTEMPTS`` (default 4 total attempts)
- ``MODALITIES_TPU_IO_RETRY_BASE_S``   (default 0.5s; doubles per retry + jitter)
"""

from __future__ import annotations

import os
import random
import time
from typing import Callable, Optional, TypeVar

from modalities_tpu.telemetry import span
from modalities_tpu.utils.logging import get_logger

logger = get_logger(__name__)

T = TypeVar("T")

RETRIABLE_EXCEPTIONS: tuple[type[BaseException], ...] = (OSError, IOError)


def _default_attempts() -> int:
    return int(os.environ.get("MODALITIES_TPU_IO_RETRY_ATTEMPTS", "4"))


def _default_base_delay_s() -> float:
    return float(os.environ.get("MODALITIES_TPU_IO_RETRY_BASE_S", "0.5"))


def retry_io(
    fn: Callable[[], T],
    what: str,
    attempts: Optional[int] = None,
    base_delay_s: Optional[float] = None,
    max_delay_s: float = 30.0,
    retriable: tuple[type[BaseException], ...] = RETRIABLE_EXCEPTIONS,
) -> T:
    """Run `fn`, retrying `retriable` failures with exponential backoff + jitter.

    The final failure re-raises the last exception unchanged, so callers keep
    their existing error contracts when storage is genuinely down."""
    from modalities_tpu.resilience.events import record_event

    attempts = attempts if attempts is not None else _default_attempts()
    base_delay_s = base_delay_s if base_delay_s is not None else _default_base_delay_s()
    last_error: Optional[BaseException] = None
    for attempt in range(max(attempts, 1)):
        try:
            if attempt == 0:
                return fn()
            with span(f"ckpt_retry/{what}"):
                return fn()
        except retriable as e:  # noqa: PERF203 — per-attempt handling is the point
            last_error = e
            if attempt + 1 >= max(attempts, 1):
                break
            delay = min(base_delay_s * (2**attempt), max_delay_s)
            delay *= 1.0 + random.uniform(0.0, 0.25)  # jitter: desync rank herds
            record_event(
                "ckpt_retry/attempt",
                what=what,
                attempt=attempt + 1,
                error=repr(e),
                next_delay_s=round(delay, 3),
            )
            logger.warning(
                "%s failed (attempt %d/%d): %r — retrying in %.2fs",
                what, attempt + 1, attempts, e, delay,
            )
            time.sleep(delay)
    assert last_error is not None
    raise last_error
