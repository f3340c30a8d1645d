"""Size the four-chip training configuration without a chip: compile its donated train
step for a *described* `v5e:2x2` at several depths and print `memory_analysis()` per
chip, so that the deepest stack that fits can be written into the configuration.

    JAX_PLATFORMS=cpu python benchmark/tools/size_x4.py --depths 32,30,28

The four-chip configuration has no files yet (PERF.md section 7, second row): it is the
one-chip configuration's YAML with the five keys under LAYOUT changed, and the depth.

A scratch script, not a test: it describes a topology as it is imported-and-run, which
only tests/ops/test_tpu_compile.py may do among the tests. It hands the program the
described devices by replacing `jax.devices` for this process (the program builds its
mesh from it and asks it whether it is on a TPU). Nothing runs; a compile that passes
is not a chip run.
"""

import argparse
import os
import sys
import tempfile
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
GIB = 1024**3
BYTES_LIMIT_GIB = 15.75  # what memory_stats() reports as bytes_limit on a v5e (PR 21)
# dp_shard 2 x tp 2 on one four-chip host, global batch 2 x 4096: the one layout the chip has run (PR 21)
LAYOUT = {"local_train_micro_batch_size": 1, "data_parallel_shard_degree": 2, "tensor_parallel_degree": 2, "world_size": 4}


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--config", default="modalities-2p7b-d6", help="the one-chip configuration to start from")
    parser.add_argument("--depths", default="32")
    parser.add_argument("--topology", default="v5e:2x2")
    args = parser.parse_args()

    import jax
    import yaml
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's programs cannot be read back
    topo = topologies.get_topology_desc(platform="tpu", topology_name=args.topology)
    devices = list(topo.devices)
    jax.devices = lambda *a, **k: devices
    jax.device_count = lambda *a, **k: len(devices)
    jax.local_devices = lambda *a, **k: devices

    from modalities_tpu.running_env.xla_flags import apply_xla_flags_from_config
    from modalities_tpu.utils.recipe_validation import build_lowered_train_step

    source = REPO / "benchmark" / "configs" / args.config / "train.yaml"
    apply_xla_flags_from_config(source)
    raw = yaml.safe_load(source.read_text())
    raw["settings"]["step_profile"]["local_train_micro_batch_size"] = LAYOUT["local_train_micro_batch_size"]
    for key in ("data_parallel_shard_degree", "tensor_parallel_degree", "world_size"):
        raw["device_mesh"]["config"][key] = LAYOUT[key]
    for depth in (int(d) for d in args.depths.split(",")):
        raw["model_raw"]["config"]["n_layer"] = depth
        with tempfile.TemporaryDirectory() as tmp:
            path = Path(tmp) / "train.yaml"
            path.write_text(yaml.safe_dump(raw, sort_keys=False))
            t0 = time.perf_counter()
            built = build_lowered_train_step(path)
            try:
                m = built.lowered.compile().memory_analysis()
            except Exception as e:  # noqa: BLE001  the compiler's refusal is the answer
                print(f"depth {depth}: refused after {time.perf_counter() - t0:.0f} s: {str(e)[:400]}", flush=True)
                continue
        peak = m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
        print(f"depth {depth}: {peak / GIB:.2f} GiB a chip (arguments {m.argument_size_in_bytes / GIB:.2f}, "
              f"temporaries {m.temp_size_in_bytes / GIB:.2f}, outputs not aliased "
              f"{(m.output_size_in_bytes - m.alias_size_in_bytes) / GIB:.2f}) of {BYTES_LIMIT_GIB}; "
              f"compiled in {time.perf_counter() - t0:.0f} s", flush=True)


if __name__ == "__main__":
    main()
