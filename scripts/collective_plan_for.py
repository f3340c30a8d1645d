"""A configuration's collective plan and memory a chip, from a compile for a *described* topology: no chip.

    JAX_PLATFORMS=cpu python scripts/collective_plan_for.py benchmark/configs/modalities-2p7b-x4/train.yaml
    ... --topology v5e:2x2 --set model_raw.config.n_layer=2 --rows 60 --hlo /root/scratch/step.hlo.txt

Prints `telemetry/collective_plan.plan_from_hlo_text` of the configuration's donated train step as the chip's
compiler leaves it (totals by mesh axis and kind, then a row a collective: the name a trace prints, kind, axis,
bytes, times a run, scope) and `memory_analysis()` a chip. The instruction names came out the same as on the chips
(PR 50), so a `sharding` change can be sized here: which collectives the partitioner wrote, which of them the
chip's compiler kept in the form asked for, and whether the step still fits. 15-20 s for the 32-layer 2.7B step.

A scratch script, not a test: it describes a topology as it runs, which only tests/ops/test_tpu_compile.py may do
among the tests (one process at a time holds libtpu's lock). It hands the program the described devices by replacing
`jax.devices` for this process, as `benchmark/tools/size_x4.py` does. Nothing runs: no time or rate comes from it.
"""

import argparse
import os
import sys
import tempfile
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
GIB = 1024**3


def _set(raw: dict, assignment: str) -> None:
    """`a.b.c=value` into the YAML's tree; the value is read as YAML (a number, `true`, a string)."""
    import yaml

    path, _, value = assignment.partition("=")
    *parents, leaf = path.split(".")
    node = raw
    for key in parents:
        node = node[key]
    node[leaf] = yaml.safe_load(value)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("config", type=Path, help="a train.yaml whose device_mesh is the layout to compile for")
    parser.add_argument("--topology", default="v5e:2x2")
    parser.add_argument("--set", action="append", default=[], metavar="KEY.PATH=VALUE", help="change a key of the YAML first")
    parser.add_argument("--rows", type=int, default=40, help="how many collectives to list one by one, largest bytes a run first")
    parser.add_argument("--hlo", type=Path, default=None, help="also write the compiled module's text there")
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
    from modalities_tpu.telemetry.collective_plan import plan_from_hlo_text
    from modalities_tpu.utils.recipe_validation import build_lowered_train_step

    apply_xla_flags_from_config(args.config)
    raw = yaml.safe_load(args.config.read_text())
    for assignment in args.set:
        _set(raw, assignment)
    with tempfile.TemporaryDirectory() as tmp:
        path = Path(tmp) / "train.yaml"
        path.write_text(yaml.safe_dump(raw, sort_keys=False))
        t0 = time.perf_counter()
        built = build_lowered_train_step(path)
        compiled = built.lowered.compile()
    seconds = time.perf_counter() - t0
    text = compiled.as_text()
    if args.hlo is not None:
        args.hlo.parent.mkdir(parents=True, exist_ok=True)
        args.hlo.write_text(text)

    mesh_axes = {name: int(size) for name, size in built.mesh_handle.mesh.shape.items() if int(size) > 1}
    plan = plan_from_hlo_text(text, mesh_axes)
    print(f"{args.config} on a described {args.topology}, mesh {mesh_axes}: compiled in {seconds:.0f} s")
    print(f"plan of {plan['module']}: {len(plan['rows'])} collectives, {plan['bytes_a_run']:,} bytes a run")
    for key, total in plan["totals"].items():
        print(f"  {key:<28} {total['count']:>3} instructions  {total['count_a_run']:>5} a run  {total['bytes_a_run']:>16,} bytes a run")
    print(f"  {'name':<28} {'kind':<18} {'axis':<12} {'bytes':>14} {'times':>5}  scope")
    for row in sorted(plan["rows"], key=lambda r: -r["bytes"] * r["times"])[: args.rows]:
        print(f"  {row['name']:<28} {row['kind']:<18} {row['axis']:<12} {row['bytes']:>14,} {row['times']:>5}  {row['scope']}")
    m = compiled.memory_analysis()
    peak = m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
    print(f"memory a chip: {peak:,} bytes = {peak / GIB:.2f} GiB (arguments {m.argument_size_in_bytes / GIB:.2f}, temporaries "
          f"{m.temp_size_in_bytes / GIB:.2f}, outputs not aliased {(m.output_size_in_bytes - m.alias_size_in_bytes) / GIB:.2f})")


if __name__ == "__main__":
    main()
