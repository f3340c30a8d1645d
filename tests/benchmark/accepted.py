"""What a test may hold the manifest to: AT LEAST what a cell was accepted with, in its order.

`BENCHMARK.json` grows: a later PR appends cells, metrics, and a new cell's name to the
`workloads` of a metric it shares. A test that compares one of those lists with `==`
against the list of its day is red from the next PR on, and only a `benchmark` PR may
edit it. So every test under `tests/benchmark/` that reads a cell's per-layer list, a
metric's `workloads`, the manifest's cells or its configurations goes through
`holds_at_least`, and the real costs of the long cells that the budget tests count with
are in one table here.
"""

from __future__ import annotations

from collections.abc import Iterable, Set


def holds_at_least(listed: Iterable[str], accepted: Iterable[str]) -> bool:
    """Every accepted name is listed. Where `accepted` is a sequence its names also come
    FIRST in `listed` and in their order (what is appended later follows them); where it
    is a set, order is not held."""
    listed = list(listed)
    if isinstance(accepted, Set):
        return set(accepted) <= set(listed)
    accepted = list(accepted)
    return listed[:len(accepted)] == accepted


def up_to(accepted: list, name: str) -> list:
    """`accepted` as far as `name`, `name` with it: what a list held when `name` had just been appended to it."""
    return accepted[:accepted.index(name) + 1]


# The cells and configurations as PR 49 found them accepted, in the order they came. A
# later cell's own test holds its place with `[*ACCEPTED_CELLS, <its name>]`.
ACCEPTED_CELLS = ["train-2p7b-4k", "train-jamba2-3b-4k", "train-kanana2-30b-8k", "train-ouro-2p6b-4k", "train-mellum2-12b-16k",
                  "train-zaya1-8b-8k", "train-qwen3next-80b-16k"]
ACCEPTED_CONFIGS = ["modalities-2p7b-d6", "jamba2-3b-d14", "kanana2-30b-a3b-d9", "ouro-2p6b-t4", "mellum2-12b-a2p5b-d12",
                    "zaya1-8b-ep2", "qwen3-next-80b-a3b-d4"]

# Seconds a run of a long cell takes on the chip, warm and where everything compiles: the
# builders' chip runs, for each cell the PR that added it (PERF.md sections 2 and 6). All
# five for one reason: a float32 reference through two gradients at `highest` precision.
# A cell that is not here costs what `test_manifest.py` counts: `run_seconds` + 60 a run,
# 90 s more for each of its two cold runs.
REAL_COST_S = {
    "train-kanana2-30b-8k": (143, 292),  # PR 30: 120-143 s warm (set-up 42 + window 40 + reference 34-40), 287-292 cold
    "train-ouro-2p6b-4k": (105, 220),  # PR 32: set-up 31-34 + window 40 + reference 20 + start and teardown; cold: set-up 118, reference 58 or 24
    "train-mellum2-12b-16k": (150, 330),  # PR 38
    "train-zaya1-8b-8k": (135, 270),  # PR 40: 12 runs of one call, two of them cold, in 1,737 s
    "train-qwen3next-80b-16k": (131, 353),  # PR 44: the final tree's seven runs in one call, the first of them cold
}
DRIVER_SECONDS = 43200


def check_seconds(run_seconds: int, usual_cells: int, long_costs: list) -> int:
    """The driver's arithmetic for a full check (2 + 14 runs a cell, 2 of a cell's cold,
    1200 s spare): `usual_cells` at `run_seconds` + 60 a run, and a cell for every
    (warm, cold) of `long_costs` at those."""
    usual = run_seconds + 60
    return (2 * usual + 14 * usual_cells * usual + 2 * 90 * usual_cells
            + sum(14 * warm + 2 * (cold - warm) for warm, cold in long_costs) + 1200)


def full_check_seconds(manifest: dict) -> int:
    """A full check of every cell `manifest` has, the long cells at their real costs."""
    cells = [w["name"] for w in manifest["workloads"]]
    long_costs = [REAL_COST_S[c] for c in cells if c in REAL_COST_S]
    return check_seconds(manifest["run_seconds"], len(cells) - len(long_costs), long_costs)
