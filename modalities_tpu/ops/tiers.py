"""Which form of an operation runs: one rule, asked here by every dispatch wrapper.

A Pallas kernel runs where the platform is a TPU and the planner that reads the call's
shapes says so (`combine_plan`, `backward_plan`, `grad_plan`, `attention_keep_plan`,
`walk_kernels`);
off a TPU the reference form runs, so CPU tests see reference numerics. No environment
variable, config key or CLI flag overrides this: two forms are compared as two commits
on the chip.

Tests reach a kernel off the chip through `interpreted_kernels()` (or a wrapper's own
`interpret=` keyword). Wrappers ask through the module (`tiers.kernels_run()`), so a
test that pretends the platform is a TPU, to lower for a described topology, patches
the one name `tiers.on_tpu`.
"""

from __future__ import annotations

import contextlib

_interpreted = False  # inside `interpreted_kernels()`


def on_tpu() -> bool:
    """A backend that fails to initialise raises here: answering "not a TPU" would
    turn every kernel into its reference without a word."""
    import jax

    return jax.devices()[0].platform == "tpu"


def kernels_run() -> bool:
    """Whether a wrapper takes its kernel: on a TPU, and off one inside `interpreted_kernels()`."""
    return _interpreted or on_tpu()


def interpret(asked: bool = False) -> bool:
    """The `interpret` a kernel call gets: compiled on a TPU unless the caller asked, interpreted
    (exact CPU emulation of the same kernel code) wherever else a call got this far."""
    return asked or not on_tpu()


@contextlib.contextmanager
def interpreted_kernels():
    """For tests, reachable from Python only: what is traced inside takes every kernel the rule
    would take on a TPU, interpreted. Read while tracing, as the platform is: a function jitted
    outside keeps the forms it was traced with."""
    global _interpreted
    before, _interpreted = _interpreted, True
    try:
        yield
    finally:
        _interpreted = before
