"""Multi-slice (dcn) mesh + hierarchical gradient reduction.

Three layers of pin, mirroring the single-slice ZeRO suite (test_zero_sharding):

- **mesh/data geometry units** — dcn degree inference, the dp axis set, the
  sampler/data-loading fold of dcn into data parallelism, and the ZeRO-1 rule
  that optimizer-state specs never carry the dcn axis (cross-slice traffic must
  stay one grad reduction; sharding moments over dcn would add a cross-slice
  all-gather to every optimizer step).
- **HLO collective profile** — the hierarchical-reduction contract on the
  lowered program: every dcn-crossing all-reduce sits OUTSIDE the microbatch
  while loop and their count does not grow with gradient_accumulation_steps
  (i.e. the slow cross-slice hop happens once per optimizer step, not once per
  microbatch), the within-slice gradient reduction stays on intra-slice groups,
  and no reduce-scatter/all-gather crosses slices on the flat dcn layout. The
  dcn-crossing test uses exact replica-group expansion (perfscope's parser) —
  a group crosses slices iff its partition ids span >= 2 dcn coordinates.
- **numerics** — dcn2 x dp4 reproduces the flat dp8 twin's losses to rtol 1e-5
  over 3 steps + eval, and ZeRO-1 composed under dcn (dcn2 x rep2 x shard2)
  matches too. jax_threefry_partitionable is off on this jax, so param init
  depends on mesh geometry: all runs warmstart from one donor init, transferred
  cross-mesh with device_put (the elastic-resume path's mechanics). Compute is
  pinned to float32 — the GPT2 default bf16 compute makes flat and grouped
  reductions differ at ~2^-8 relative, drowning the 1e-5 parity signal.
"""

import re

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from modalities_tpu.exceptions import ConfigError
from modalities_tpu.models.model import MixedPrecisionSpec
from modalities_tpu.parallel.sharding import zero_partition_spec
from modalities_tpu.running_env.device_mesh import (
    get_data_loading_info,
    get_device_mesh,
    infer_num_slices,
)
from modalities_tpu.telemetry.perfscope import _parse_replica_groups
from tests.models.test_gpt2_model import tiny_gpt2
from tests.training.test_train_step import _batch, _builder

DCN, DP_SHARD = 2, 4


def _dcn_mesh(zero_stage=0, dp_replicate=1, dp_shard=None):
    if dp_shard is None:
        dp_shard = DP_SHARD // dp_replicate
    return get_device_mesh(
        device_type="cpu",
        data_parallel_replicate_degree=dp_replicate,
        data_parallel_shard_degree=dp_shard,
        dcn_parallel_degree=DCN,
        world_size=8,
        zero_stage=zero_stage,
    )


def _f32_model():
    # bf16 compute reorders the grouped reduction past the 1e-5 parity window
    model = tiny_gpt2("pytorch_flash")
    model.update_train_spec(mixed_precision=MixedPrecisionSpec(compute_dtype="float32"))
    return model


# ---------------------------------------------------------------- mesh geometry


class _FakeSliceDevice:
    def __init__(self, slice_index):
        self.slice_index = slice_index


def test_infer_num_slices_from_device_attributes():
    assert infer_num_slices([_FakeSliceDevice(i // 4) for i in range(8)]) == 2
    assert infer_num_slices([_FakeSliceDevice(0) for _ in range(4)]) == 1
    # CPU/GPU devices carry no slice_index: single slice
    assert infer_num_slices([object(), object()]) == 1
    assert infer_num_slices([]) == 1


def test_dcn_mesh_geometry_and_dp_axis_names():
    handle = _dcn_mesh()
    assert handle.axis_names == ("dcn", "dp_shard")
    assert dict(zip(handle.axis_names, handle.mesh.devices.shape)) == {"dcn": 2, "dp_shard": 4}
    assert handle.dcn_degree == 2
    assert handle.dp_degree == 8  # dcn folds into data parallelism
    assert handle.dp_axis_names == ("dcn", "dp_shard")

    # auto-infer (-1) on sliceless CPU devices: no dcn axis materializes
    auto = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)
    assert "dcn" not in auto.axis_names
    assert auto.dcn_degree == 1 and auto.dp_degree == 8
    assert auto.dp_axis_names == ("dp_shard",)


def test_dcn_degree_validation():
    # degrees must multiply out to the world size, dcn included
    with pytest.raises(ConfigError, match="dcn_parallel_degree"):
        get_device_mesh(
            device_type="cpu", data_parallel_shard_degree=4, dcn_parallel_degree=3, world_size=8
        )
    # an explicit degree that contradicts real multi-slice devices is a config
    # error, not a silent mis-mapped mesh
    fakes = [_FakeSliceDevice(i // 4) for i in range(8)]
    with pytest.raises(ConfigError, match="dcn_parallel_degree"):
        get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, dcn_parallel_degree=1, devices=fakes)


def test_data_loading_folds_dcn_into_the_batch_split():
    from modalities_tpu.dataloader.sampler_factory import BatchSamplerFactory, SamplerFactory

    handle = _dcn_mesh()
    # single-controller process owns every dp coordinate -> one loading rank
    assert get_data_loading_info(handle) == (1, 0)
    sampler = SamplerFactory.create_resumable_distributed_multi_dim_sampler(
        dataset=list(range(64)), device_mesh=handle
    )
    assert sampler.num_replicas == 1 and sampler.rank == 0
    # the process-level batch covers all dcn*dp_shard ranks' rows
    batch_sampler = BatchSamplerFactory.create_batch_sampler(
        sampler, batch_size=2, device_mesh=handle
    )
    assert batch_sampler.batch_size == 2 * 8


def test_zero_specs_never_carry_dcn():
    mesh = _dcn_mesh(zero_stage=1, dp_replicate=2, dp_shard=2).mesh
    # the replica axis widens the shard dim; dcn must not appear in any spec
    widened = zero_partition_spec((64, 32), P("dp_shard", None), mesh)
    assert widened == P(("dp_replicate", "dp_shard"), None)
    unsharded = zero_partition_spec((16, 64), P(), mesh)
    for spec in (widened, unsharded):
        axes = {
            a
            for entry in spec
            if entry is not None
            for a in (entry if isinstance(entry, tuple) else (entry,))
        }
        assert "dcn" not in axes, spec


# ------------------------------------------------------------- HLO collective pin


def _computations(hlo: str) -> dict[str, list[str]]:
    """HLO text split into named computation bodies (ENTRY included)."""
    comps: dict[str, list[str]] = {}
    name = None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    return comps


def _crosses_slices(groups: list[list[int]]) -> bool:
    # canonical axis order puts dcn outermost: partition ids unravel row-major,
    # so slice(pid) = pid // (world / dcn)
    per_slice = 8 // DCN
    return any(len({p // per_slice for p in g}) > 1 for g in groups)


def _collective_profile(hlo: str, op: str):
    """(computation, shape, groups) for every `op` with explicit replica groups."""
    out = []
    for comp, lines in _computations(hlo).items():
        for line in lines:
            if f" {op}(" not in line:
                continue
            groups = _parse_replica_groups(line)
            if groups:
                # a combined all-reduce has a tuple shape: "(f32[..], f32[..])"
                shape = re.search(rf"= (\(.*?\)|\S+) {op}\(", line).group(1)
                out.append((comp, shape, groups))
    return out


def _is_scalar(shape: str) -> bool:
    return all(dims == "" for dims in re.findall(r"\[([0-9,]*)\]", shape))


@pytest.fixture(scope="module")
def dcn_compiles():
    """Compiled train-step HLO on the dcn2 x dp4 mesh for acc 1 and 2, plus the
    ZeRO-1 composition (dcn2 x rep2 x shard2). materialize=False: no init run."""
    out = {}
    for key, mesh, acc in (
        ("acc1", _dcn_mesh(), 1),
        ("acc2", _dcn_mesh(), 2),
        ("zero", _dcn_mesh(zero_stage=1, dp_replicate=2, dp_shard=2), 1),
    ):
        raw = _batch(np.random.default_rng(3), acc, 8, 16)
        abstract = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), raw)
        fns = _builder(_f32_model(), mesh, acc=acc, clip=1.0).build(seed=0, materialize=False)
        out[key] = fns.lower_train_step(abstract).compile().as_text()
    return out


def test_one_cross_slice_reduction_per_optimizer_step(dcn_compiles):
    profiles = {
        key: _collective_profile(hlo, "all-reduce") for key, hlo in dcn_compiles.items()
    }
    cross = {
        key: [(c, s) for c, s, g in prof if _crosses_slices(g)]
        for key, prof in profiles.items()
    }
    # the accumulated-grad reduction crosses slices (non-scalar payload present)
    assert any(not _is_scalar(s) for _, s in cross["acc1"])
    # hierarchical contract: cross-slice all-reduce count is per OPTIMIZER STEP —
    # unchanged under gradient accumulation; the ZeRO-1 composition has them too
    # (how many combined ops XLA makes of them there is the combiner's business:
    # it merges only all-reduces over the same groups, docs/known_failures.md)
    assert len(cross["acc1"]) == len(cross["acc2"]) > 0 and cross["zero"]
    # ... and none of them lives inside a while body (the microbatch loop): the
    # per-microbatch reduction stays on fast intra-slice groups
    for key, hlo in dcn_compiles.items():
        bodies = set(re.findall(r"body=%?([\w.\-]+)", hlo))
        in_body = [(c, s) for c, s in cross[key] if c in bodies]
        assert not in_body, f"{key}: cross-slice all-reduce inside a loop body: {in_body}"
    # the within-slice gradient reduction exists and stays intra-slice
    intra_nonscalar = [
        (c, s) for c, s, g in profiles["acc1"] if not _crosses_slices(g) and not _is_scalar(s)
    ]
    assert intra_nonscalar, "within-slice grad reduction disappeared"


def test_reduce_scatter_and_gather_stay_intra_slice(dcn_compiles):
    # flat dcn layout: parameter/grad movement never crosses the slow fabric.
    # (This CPU backend decomposes reduce-scatter, so the all-reduce profile
    # above is the primary signal; the literal ops, when emitted, must comply.)
    for op in ("reduce-scatter", "all-gather"):
        crossing = [
            (c, s)
            for c, s, g in _collective_profile(dcn_compiles["acc1"], op)
            if _crosses_slices(g)
        ]
        assert not crossing, f"{op} crossing slices on the flat dcn mesh: {crossing}"


# ------------------------------------------------------------------- numerics


def _run(fns, state, raw, steps=3):
    batch = fns.put_batch(raw)
    losses = []
    for _ in range(steps):
        state, metrics = fns.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    eval_batch = fns.put_batch(
        {
            "samples": {k: v[0] for k, v in raw["samples"].items()},
            "targets": {k: v[0] for k, v in raw["targets"].items()},
        },
        has_acc_dim=False,
    )
    losses.append(float(fns.eval_step(state, eval_batch)["loss"]))
    return losses


def _warmstart(donor_state, fns):
    # cross-mesh transfer: re-home the donor's values onto this mesh's shardings
    return jax.tree.map(
        lambda s, d: jax.device_put(np.asarray(s), d.sharding),
        donor_state,
        fns.app_state_handle.state,
    )


# back in tier 1 since PR 47 (9 s under the suite's compile rule; the HLO profile beside it holds the structure, this the numbers)
def test_dcn_losses_match_flat_dp_twin():
    """dcn2 x dp4 == dp8 to rtol 1e-5 (3 train steps + eval) — the multi-slice
    acceptance pin. (The ZeRO-1 x dcn composition is pinned structurally above —
    spec rule + HLO profile — and runs end-to-end in dryrun_multichip.)"""
    raw = _batch(np.random.default_rng(7), 1, 8, 16)
    mesh_dp8 = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)

    fns_flat = _builder(_f32_model(), mesh_dp8, clip=1.0).build(seed=0)
    # host-side snapshot BEFORE stepping: train_step donates the state buffers
    donor = jax.tree.map(np.asarray, fns_flat.app_state_handle.state)
    losses_flat = _run(fns_flat, fns_flat.app_state_handle.state, raw)

    fns_dcn = _builder(_f32_model(), _dcn_mesh(), clip=1.0).build(seed=0)
    losses_dcn = _run(fns_dcn, _warmstart(donor, fns_dcn), raw)
    np.testing.assert_allclose(losses_flat, losses_dcn, rtol=1e-5)

    # and it actually trains: strictly decreasing finite losses
    train_losses = losses_dcn[:-1]
    assert all(np.isfinite(train_losses))
    assert train_losses == sorted(train_losses, reverse=True)
