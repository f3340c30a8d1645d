"""Compile-only validation of the v5p acceptance recipes (BASELINE.md).

Answers two questions about a pod-scale training config without a pod (or any
hardware — a virtual CPU mesh suffices):

1. Does the full sharded train-step program LOWER? `jax.jit(...).lower(...)` over the
   config's real mesh/shardings runs XLA's SPMD partitioner front-end: any
   shape/sharding mismatch, invalid collective layout, or tracing error in the
   pp/dp/tp/cp composition surfaces here, exactly as it would on chips.
2. Does the state FIT? Params / optimizer state / gradients are measured exactly from
   the abstract state tree and its NamedShardings (`sharding.shard_shape`); activations
   and the lm-head working set are estimated with a documented formula keyed to the
   remat mode. The result is a per-chip HBM budget report against the v5p's 95 GB.

No parameter buffer is ever allocated: the component graph is declarative and
TrainStepBuilder.build(materialize=False) keeps the state abstract, so a 7B recipe
validates in seconds on a laptop-class host.
"""

from __future__ import annotations

from pathlib import Path
from typing import Optional

import numpy as np

# bf16 TPU v5p: 95 GB usable HBM per chip (96 GB minus runtime reservation)
V5P_HBM_BUDGET_BYTES = 95 * 1024**3

# Synthetic checkpoint folder accepted by every number_conversion regex — used when a
# warmstart recipe is validated without a real checkpoint on disk. The numbers MUST
# stay consistent with the warmstart recipe's training_target (the instantiation-model
# validators recompute tokens-per-step from them).
_FAKE_WARMSTART_FOLDER = (
    "data/checkpoints/validation/eid-seen_steps_100000-seen_tokens_13107200000"
    "-target_steps_100000-target_tokens_13107200000"
)


def _per_device_bytes(abstract_leaf, sharding) -> int:
    """Exact bytes one device holds for a (possibly sharded) array."""
    shape = tuple(abstract_leaf.shape)
    itemsize = np.dtype(abstract_leaf.dtype).itemsize
    if sharding is not None and hasattr(sharding, "shard_shape") and shape:
        shape = sharding.shard_shape(shape)
    return int(np.prod(shape, dtype=np.int64)) * itemsize if shape else itemsize


def _matched_shardings(abstract_tree, sharding_tree, caveats: Optional[list] = None) -> tuple:
    """(state leaves, sharding leaves) with matching lengths. On a leaf-count
    mismatch (a sharding tree that collapsed Nones) every leaf is treated as
    REPLICATED — which can inflate per-chip bytes by up to world_size x and wrongly
    fail the budget check — so the fallback is surfaced, never silent."""
    import jax
    import warnings

    leaves = jax.tree.leaves(abstract_tree)
    shardings = jax.tree.leaves(sharding_tree) if sharding_tree is not None else [None] * len(leaves)
    if len(shardings) != len(leaves):
        msg = (
            f"sharding tree has {len(shardings)} leaves but the state tree has "
            f"{len(leaves)}: treating every leaf as REPLICATED, which can inflate "
            "per-chip bytes by up to world_size x and wrongly fail the budget check"
        )
        if caveats is not None:
            caveats.append(msg)
        warnings.warn(msg, stacklevel=2)
        shardings = [None] * len(leaves)
    return leaves, shardings


def _tree_per_device_bytes(abstract_tree, sharding_tree, caveats: Optional[list] = None) -> int:
    leaves, shardings = _matched_shardings(abstract_tree, sharding_tree, caveats)
    return sum(_per_device_bytes(x, s) for x, s in zip(leaves, shardings))


def _estimate_activation_bytes(model, mesh_handle, step_profile) -> dict:
    """Documented per-chip activation estimate for the GPT2LLM family.

    Let b = local microbatch rows, s_l = seq / cp, d_l = n_embd / tp, f_l = ffn / tp,
    act = 2 bytes (bf16 compute). Per layer the live set during backward is:
      - full remat: only the block input residual stream survives the forward
        (b*s_l*d_l) plus ONE block's recompute working set (counted once, not per
        layer): ~ b*s_l*(4*d_l + 3*f_l).
      - no remat: qkv+attn-out+norms+residuals ~ 10*d_l plus swiglu gate/up/act
        ~ 3*f_l per token, all stored for backward.
    Flash/ring attention never materializes the [s, s] score matrix, so no s^2 term.
    The lm head adds b*s_l*vocab/tp fp32 logits UNLESS lm_head_chunk_size caps it at
    b*chunk*vocab/tp.
    """
    spec = getattr(model, "config_spec", None)
    required = ("n_embd", "n_layer", "vocab_size", "activation", "ffn_hidden")
    if spec is None or any(not hasattr(spec, a) for a in required):
        # validating a non-GPT2 recipe (CoCa/ViT/...): state bytes are still exact,
        # but the activation formula is GPT2LLM-specific — report that clearly
        # instead of crashing mid-report with an AttributeError
        return {
            "remat_mode": None,
            "layer_activation_bytes": 0,
            "lm_head_bytes": 0,
            "total": 0,
            "unavailable": (
                f"activation estimate unavailable for model family "
                f"{type(model).__name__}: the formula is GPT2LLM-specific; "
                "per-chip totals below cover params/optimizer/gradients only"
            ),
        }
    degrees = mesh_handle.degrees
    tp = max(1, degrees.get("tp", 1))
    cp = max(1, degrees.get("cp", 1))
    pp = max(1, degrees.get("pp", 1))

    b = step_profile.local_train_micro_batch_size
    s_l = step_profile.sequence_length // cp
    d_l = spec.n_embd // tp
    ffn = spec.swiglu_hidden if spec.activation == "swiglu" else spec.ffn_hidden
    f_l = (ffn or 4 * spec.n_embd) // tp
    n_layer_local = -(-spec.n_layer // pp)
    act = 2  # bf16

    mode = str(getattr(spec, "remat_variant", None) or "none")
    tokens = b * s_l
    if "full" in mode:
        per_layer = tokens * d_l * act
        working_set = tokens * (4 * d_l + 3 * f_l) * act  # one block recompute
        layer_bytes = n_layer_local * per_layer + working_set
    elif "selective" in mode:
        # between full and none; assume half the no-remat live set
        layer_bytes = n_layer_local * tokens * (10 * d_l + 3 * f_l) * act // 2
    else:
        layer_bytes = n_layer_local * tokens * (10 * d_l + 3 * f_l) * act

    chunk = getattr(spec, "lm_head_chunk_size", None)
    vocab_l = spec.vocab_size // tp if mesh_handle.enable_loss_parallel else spec.vocab_size
    head_rows = b * (chunk if chunk else s_l)
    head_bytes = head_rows * vocab_l * 4  # fp32 logits for the live chunk / sequence

    return {
        "remat_mode": mode,
        "layer_activation_bytes": int(layer_bytes),
        "lm_head_bytes": int(head_bytes),
        "total": int(layer_bytes + head_bytes),
    }


class BuiltTrainStep:
    """Everything `validate_recipe` and `telemetry.perfscope` need from one
    declarative component build: the abstract-state step functions, the live
    components, the mesh, the abstract batch, and the lowering outcome."""

    def __init__(self, fns, components, mesh_handle, batch_abstract, world_size,
                 lowered, lowering: str):
        self.fns = fns
        self.components = components
        self.mesh_handle = mesh_handle
        self.batch_abstract = batch_abstract
        self.world_size = world_size
        self.lowered = lowered  # None when lowering failed
        self.lowering = lowering  # "ok" | "failed: ..."


def build_lowered_train_step(
    config_file_path: Path,
    warmstart_checkpoint_folder: Optional[str] = None,
    raise_on_lowering_failure: bool = True,
) -> BuiltTrainStep:
    """Build the recipe's full sharded train step over its real mesh (abstract
    state, no parameter buffers) and lower it. The shared front half of
    `validate_recipe` and `telemetry.perfscope.perfscope_for_config`. Requires
    jax.device_count() >= the config's world_size."""
    import jax

    from modalities_tpu.config.instantiation_models import RecipeValidationInstantiationModel
    from modalities_tpu.main import Main
    from modalities_tpu.parallel.sharding import batch_sharding

    config_file_path = Path(config_file_path)

    def warmstart_env(key: str):
        if key in ("checkpoint_paths", "checkpoint_folder_path"):
            return warmstart_checkpoint_folder or _FAKE_WARMSTART_FOLDER
        raise ValueError(f"Unknown warmstart_env variable {key!r}")

    main_obj = Main(
        config_file_path,
        additional_resolver_funs={"warmstart_env": warmstart_env},
        experiment_id="recipe_validation",
    )
    components = main_obj.build_components(RecipeValidationInstantiationModel)

    mesh_handle = components.device_mesh
    world_size = int(np.prod(list(mesh_handle.mesh.shape.values())))
    if jax.device_count() < world_size:
        raise RuntimeError(
            f"recipe needs {world_size} devices but only {jax.device_count()} are "
            "visible — run under JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={world_size}"
        )

    step_profile = components.settings.step_profile
    fns = Main.build_step_functions(components, materialize=False)

    # --- abstract global batch with the real data sharding
    acc = step_profile.gradient_accumulation_steps
    rows = step_profile.local_train_micro_batch_size * mesh_handle.dp_degree
    seq = step_profile.sequence_length
    data_sharding = batch_sharding(mesh_handle)
    import jax.sharding as js

    spec3 = js.NamedSharding(
        data_sharding.mesh, js.PartitionSpec(None, *tuple(data_sharding.spec))
    )
    tok = jax.ShapeDtypeStruct((acc, rows, seq), np.int32, sharding=spec3)
    model = fns.app_state_handle.model
    batch_abstract = {
        "samples": {model.sample_key: tok},
        "targets": {components.loss_fn.target_key: tok},
    }

    lowered = None
    try:
        lowered = fns.lower_train_step(batch_abstract)
        lowering = "ok"
    except Exception as e:  # report the partitioning/tracing failure, don't crash
        if raise_on_lowering_failure:
            raise
        lowering = f"failed: {type(e).__name__}: {str(e)[:500]}"
    return BuiltTrainStep(
        fns, components, mesh_handle, batch_abstract, world_size, lowered, lowering
    )


def validate_recipe(
    config_file_path: Path,
    hbm_budget_bytes: int = V5P_HBM_BUDGET_BYTES,
    warmstart_checkpoint_folder: Optional[str] = None,
    compile_memory_check: bool = False,
) -> dict:
    """Build the recipe's train step over its real mesh, lower it, and report the
    per-chip memory budget. Requires jax.device_count() >= the config's world_size
    (use XLA_FLAGS=--xla_force_host_platform_device_count=N JAX_PLATFORMS=cpu, or let
    the `benchmark validate_recipe` CLI re-exec with them set)."""
    import jax

    config_file_path = Path(config_file_path)
    built = build_lowered_train_step(
        config_file_path,
        warmstart_checkpoint_folder=warmstart_checkpoint_folder,
        raise_on_lowering_failure=False,
    )
    components = built.components
    mesh_handle = built.mesh_handle
    world_size = built.world_size
    step_profile = components.settings.step_profile
    fns = built.fns
    model = fns.app_state_handle.model
    lowered, lowering = built.lowered, built.lowering

    xla_memory = None
    if compile_memory_check and lowered is not None:
        # VERDICT r4 #7: back the activation FORMULA with the compiler's own
        # per-device accounting. The virtual-mesh CPU compile runs the same
        # GSPMD partitioning, so temp_size (all per-device intermediates:
        # activations kept for backward + workspace + gradient buffers) is an
        # independent order-of-magnitude check on the estimate. It is NOT a
        # TPU HBM measurement (CPU scheduling/fusion differ) — disagreement is a
        # flag to investigate, not a verdict. A compile failure is recorded HERE,
        # never conflated with the lowering verdict: this diagnostic must not
        # flip a lowering-green recipe to CLI exit 1 with a misleading cause.
        try:
            stats = lowered.compile().memory_analysis()
            xla_memory = {
                "temp_bytes": int(stats.temp_size_in_bytes),
                "argument_bytes": int(stats.argument_size_in_bytes),
                "output_bytes": int(stats.output_size_in_bytes),
                "backend": "cpu_virtual_mesh",
            }
        except Exception as e:
            xla_memory = {"error": f"{type(e).__name__}: {str(e)[:500]}"}

    # --- exact per-chip state bytes from the shardings
    state = fns.app_state_handle.state
    shardings = fns.app_state_handle.state_shardings
    budget_warnings: list = []
    param_leaves, param_shardings = _matched_shardings(
        state.params, shardings.params, budget_warnings
    )
    params_pd = sum(_per_device_bytes(x, s) for x, s in zip(param_leaves, param_shardings))
    opt_pd = _tree_per_device_bytes(state.opt_state, shardings.opt_state, budget_warnings)
    # gradients mirror the param shardings; accumulated in reduce_dtype (fp32).
    # Same length-matched pairing as the byte counts: a collapsed sharding tree must
    # fall back to replicated counting, not zip-truncate leaves to grads_pd=0
    param_count_pd = sum(
        int(np.prod(s.shard_shape(tuple(x.shape)) if hasattr(s, "shard_shape") else x.shape))
        for x, s in zip(param_leaves, param_shardings)
    )
    grads_pd = param_count_pd * 4
    act = _estimate_activation_bytes(model, mesh_handle, step_profile)
    if "unavailable" in act:  # surface through the same channel as budget caveats
        budget_warnings.append(act["unavailable"])
    total_pd = params_pd + opt_pd + grads_pd + act["total"]

    if xla_memory is not None and "temp_bytes" in xla_memory and act["total"] > 0:
        # what the compiler calls "temp" is every per-device intermediate held
        # across the step — the formula's analogue is activations + fp32 grads
        formula_bytes = act["total"] + grads_pd
        ratio = xla_memory["temp_bytes"] / max(1, formula_bytes)
        xla_memory["formula_activations_plus_grads_bytes"] = int(formula_bytes)
        xla_memory["temp_over_formula"] = round(ratio, 3)
        # Known graph delta on the virtual-mesh compile: the dao_flash tier exists
        # only on TPU, so the CPU compile runs the SDPA fallback whose backward
        # saves O(S^2) attention probabilities — bytes the TPU flash kernel (custom
        # vjp, blockwise recompute) NEVER materializes. Quantify it so the raw
        # ratio is interpretable instead of alarming.
        spec = getattr(model, "config_spec", None)
        if spec is not None and getattr(spec, "attention_impl", None) == "dao_flash":
            degrees = mesh_handle.degrees
            s_l = step_profile.sequence_length // max(1, degrees.get("cp", 1))
            h_l = max(1, spec.n_head_q // max(1, degrees.get("tp", 1)))  # heads/chip
            b = step_profile.local_train_micro_batch_size
            # fwd-saved probs [B, Hq_local, S_l, S_l] fp32, one copy per layer that
            # KEEPS residuals: all local layers without remat, ~one block's
            # recompute working set under full remat
            mode = str(getattr(spec, "remat_variant", None) or "none")
            layers_keeping = (
                1 if "full" in mode else -(-spec.n_layer // max(1, degrees.get("pp", 1)))
            )
            s2 = layers_keeping * b * h_l * s_l * s_l * 4
            xla_memory["cpu_sdpa_fallback_s2_residuals_bytes"] = int(s2)
            adj = (xla_memory["temp_bytes"] - s2) / max(1, formula_bytes)
            xla_memory["temp_minus_s2_over_formula"] = round(adj, 3)
        xla_memory["disagrees_gt_15pct"] = not (0.85 <= ratio <= 1.15)
        if xla_memory["disagrees_gt_15pct"]:
            budget_warnings.append(
                f"XLA compiled temp ({xla_memory['temp_bytes'] / 1024**3:.2f} GiB/chip) "
                f"disagrees with the activation+grad formula ({formula_bytes / 1024**3:.2f} "
                f"GiB/chip) by more than 15% (ratio {ratio:.2f}); inspect "
                "xla_compiled_memory for the known CPU-graph deltas (SDPA s^2 "
                "residuals, unfused CPU scheduling) before re-deriving the estimate"
            )

    num_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(state.params))
    report = {
        "config": str(config_file_path),
        "world_size": world_size,
        "mesh": {k: v for k, v in mesh_handle.degrees.items()},
        "num_params": num_params,
        "lowering": lowering,
        "per_device": {
            "params_bytes": params_pd,
            "optimizer_bytes": opt_pd,
            "gradient_bytes": grads_pd,
            "activation_estimate": act,
            **({"xla_compiled_memory": xla_memory} if xla_memory is not None else {}),
            "total_bytes": total_pd,
            "total_gib": round(total_pd / 1024**3, 3),
        },
        "hbm_budget_bytes": int(hbm_budget_bytes),
        "fits_budget": bool(total_pd < hbm_budget_bytes),
    }
    if budget_warnings:
        report["warnings"] = budget_warnings
    return report


def run_validation_subprocess(
    config_file_path: Path,
    hbm_budget_bytes: int = V5P_HBM_BUDGET_BYTES,
    warmstart_checkpoint_folder: Optional[str] = None,
    compile_memory_check: bool = False,
) -> dict:
    """Spawn `python -m modalities_tpu.utils.recipe_validation` in a child process
    with the CPU backend forced and world_size virtual devices, so validation works
    from any ambient environment (including one whose JAX already claimed a TPU or
    was initialized with too few devices). Returns the parsed report."""
    import json
    import os
    import re
    import subprocess
    import sys

    import yaml

    with open(config_file_path) as f:
        raw = yaml.safe_load(f)
    try:
        world_size = int(raw["device_mesh"]["config"]["world_size"])
    except (KeyError, TypeError, ValueError) as e:
        raise ValueError(
            f"{config_file_path}: could not read a literal device_mesh.config.world_size "
            "— recipe validation needs it to size the virtual device pool"
        ) from e

    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags + f" --xla_force_host_platform_device_count={world_size}").strip()

    cmd = [
        sys.executable,
        "-m",
        "modalities_tpu.utils.recipe_validation",
        str(config_file_path),
        "--hbm_budget_bytes",
        str(int(hbm_budget_bytes)),
    ]
    if warmstart_checkpoint_folder:
        cmd += ["--warmstart_checkpoint_folder", warmstart_checkpoint_folder]
    if compile_memory_check:
        cmd += ["--compile_memory_check"]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"recipe validation failed for {config_file_path} (exit {proc.returncode}):\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-4000:]}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _main() -> None:
    import argparse
    import json

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("config_file_path", type=Path)
    parser.add_argument("--hbm_budget_bytes", type=int, default=V5P_HBM_BUDGET_BYTES)
    parser.add_argument("--warmstart_checkpoint_folder", default=None)
    parser.add_argument("--compile_memory_check", action="store_true")
    args = parser.parse_args()
    report = validate_recipe(
        args.config_file_path,
        hbm_budget_bytes=args.hbm_budget_bytes,
        warmstart_checkpoint_folder=args.warmstart_checkpoint_folder,
        compile_memory_check=args.compile_memory_check,
    )
    print(json.dumps(report))


if __name__ == "__main__":
    _main()
