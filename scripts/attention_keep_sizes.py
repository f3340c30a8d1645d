"""What a block under `full` remat keeps beside its input (the flash kernel's o and lse, the gated delta rule's o and group
states) costs a cell, without a chip: compile the cell's donated train step for a *described* v5e at every rung of the plan's
ladder (nothing kept, the flash kernel's two and, where the stack holds rule layers, the rule's two beside them) and print
`memory_analysis()` of each beside what `training/activation_checkpointing.attention_keep_plan` counted and said at the v5e's limit.

    JAX_PLATFORMS=cpu python scripts/attention_keep_sizes.py --configs zaya1-8b-ep2,kanana2-30b-a3b-d9

The readings `KEEP_FLASH_WORKING_SETS` and `KEEP_BLOCK_WORKING_INPUTS` were fitted to (PERF.md section 6, PR 41 and PR 42); run it
again when a cell, a kernel's residuals or the compiler changes. About 40 s a compile. A scratch script, not a test: it
describes a topology as it runs and hands the program the described device by replacing `jax.devices` for this process
(`benchmark/tools/size_x4.py` does the same for four chips). Nothing runs; a compile that passes is not a chip run.
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
BYTES_LIMIT = int(15.75 * 2**30)  # what memory_stats() reports as bytes_limit on a v5e (PR 21)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--configs", default=",".join(sorted(p.name for p in (REPO / "benchmark" / "configs").iterdir() if (p / "train.yaml").exists())))
    args = parser.parse_args()

    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's programs cannot be read back
    devices = [topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]]
    jax.devices = jax.local_devices = lambda *a, **k: devices
    jax.device_count = lambda *a, **k: len(devices)

    from modalities_tpu.running_env.xla_flags import apply_xla_flags_from_config
    from modalities_tpu.training import activation_checkpointing
    from modalities_tpu.utils.recipe_validation import build_lowered_train_step

    plan = activation_checkpointing.attention_keep_plan
    for config in args.configs.split(","):
        source = REPO / "benchmark" / "configs" / config / "train.yaml"
        apply_xla_flags_from_config(source)
        first_rung = len(activation_checkpointing.KEEPS)  # from the step that keeps nothing up the ladder to the one that keeps all it can
        while first_rung >= 0:
            counted, forced = {}, {}

            def planned(flash_calls, **given):  # the described device reports no limit: count at the v5e's, keep the rung asked for
                counted.update(plan(flash_calls, **{**given, "bytes_limit": BYTES_LIMIT, "first_rung": 0}))
                forced.update(plan(flash_calls, **{**given, "bytes_limit": None, "first_rung": first_rung}))
                return dict(forced)

            activation_checkpointing.attention_keep_plan = planned
            t0 = time.perf_counter()
            m = build_lowered_train_step(source).lowered.compile().memory_analysis()
            peak = m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
            print(json.dumps({"config": config, "keeping": list(forced["kept"]), "compiler_peak_bytes": peak,
                              "over_limit_by": max(peak - BYTES_LIMIT, 0), "plan_at_the_limit": counted,
                              "seconds": round(time.perf_counter() - t0)}), flush=True)
            first_rung = forced["rung"] - 1  # `rung` of a plan with nothing to keep is 0: one program


if __name__ == "__main__":
    main()
