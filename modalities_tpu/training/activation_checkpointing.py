"""Activation checkpointing variants mapped onto jax.checkpoint policies
(reference: src/modalities/training/activation_checkpointing/activation_checkpointing.py).

Reference variants -> TPU equivalents:
- FULL: remat every transformer block (``nn.remat`` around the scanned block). A block keeps its
  input, and since PR 41, where they fit, the flash kernel's ``o`` and ``lse`` (``lse`` as the kernel
  writes it since PR 42, ``[B, H, 1, S]`` rows of numbers), so that the recomputed forward does not run
  ``flash_attention_fwd`` again: the
  backward kernels read q, k, v (projections, made again) and those two, which only the forward
  kernel makes. Who decides is the program, not a key: ``attention_keep_plan`` below, called while
  the train step is traced (``training/train_step.py``), and ``Trainer._preflight_memscope`` under
  it, which builds the step without keeping where the compiler finds the keeping one over budget.
- SELECTIVE_LAYER (every ac_freq-th block): honored on the unrolled-blocks model
  (``scan_layers=False``) where each layer gets its own remat decision; the
  scan-over-layers representation traces ONE body for every layer, so ac_freq > 1
  there raises with instructions rather than silently rematting everything.
- SELECTIVE_OP (save-list over ops: mm/SDPA/max/reduce_scatter): a jax.checkpoint
  policy built from `save_only_these_names` / `dots_with_no_batch_dims_saveable`;
  the attention output carries a ``checkpoint_name("attn_out")`` save point (which spares the
  XLA tiers' attention, not the Pallas kernel: its backward wants ``lse`` too). A user's
  ``save_list`` is taken as given; the plan does not touch it.
"""

from __future__ import annotations

from enum import Enum
from typing import Optional

import jax


class ActivationCheckpointingVariants(str, Enum):
    FULL_ACTIVATION_CHECKPOINTING = "full_activation_checkpointing"
    SELECTIVE_LAYER_ACTIVATION_CHECKPOINTING = "selective_layer_activation_checkpointing"
    SELECTIVE_OP_ACTIVATION_CHECKPOINTING = "selective_op_activation_checkpointing"


_NAMED_POLICIES = {
    "matmul": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    "everything": jax.checkpoint_policies.everything_saveable,
    "nothing": jax.checkpoint_policies.nothing_saveable,
}


def save_list_policy(save_list: tuple[str, ...]):
    """Build a checkpoint policy from op-name hints (reference SAVE_DICT :67-83).

    The reference lists aten ops (mm every 2nd, SDPA, reduce_scatter, max); the closest
    XLA-level notion is 'save dot-product results, recompute elementwise', which
    `dots_with_no_batch_dims_saveable` expresses. Named checkpoints from
    ``jax.ad_checkpoint.checkpoint_name`` are honored via save_only_these_names.
    """
    names = tuple(n for n in save_list if n not in _NAMED_POLICIES)
    base = None
    for n in save_list:
        if n in _NAMED_POLICIES:
            base = _NAMED_POLICIES[n]
    if names and base is not None:
        named = jax.checkpoint_policies.save_only_these_names(*names)
        return jax.checkpoint_policies.save_from_both_policies(base, named)
    if names:
        return jax.checkpoint_policies.save_only_these_names(*names)
    if base is not None:
        return base
    return jax.checkpoint_policies.dots_with_no_batch_dims_saveable


KEEP_VERDICTS = ("fits", "over_count", "fell_back_in_preflight", "no_remat")
# what a block's backward holds beside what is kept, as the count has it: twice the widest flash call's own operands and
# results (`backward_bytes`: the kernel's, and the model's layout of them round it; lse and delta in it as the dense rows
# they are since PR 42, no lane tile a number) and 26 block inputs for everything else of a block (norms, projections, the
# feed-forward or expert layer). Fitted on a v5e to `memory_analysis()` of the keeping step of the two cells the count is
# nearest to (`scripts/attention_keep_sizes.py`, PERF.md section 6, PR 42: 15.31 GiB counted 15.37, 13.50 counted 13.60)
# and checked on two more, where it counts high (13.92 counted 14.23, 14.84 counted 15.22); under PR 41's layout the pair
# was 2 and 24 (13.63 counted 13.72, 16.23 counted 16.24). The compiler is the judge (`Trainer._preflight_memscope`), the
# count only spares a step that cannot keep a second trace and lowering
KEEP_FLASH_WORKING_SETS, KEEP_BLOCK_WORKING_INPUTS = 2, 26


def attention_keep_plan(flash_calls: Optional[dict], *, state_bytes: int, gradient_bytes: int, bytes_limit: Optional[int],
                        allowed: bool = True) -> dict:
    """Whether the blocks under `full` remat keep the flash kernel's o and lse beside their input, from what the program
    sees before it compiles: `flash_calls` (the model's `remat_flash_calls`: the rematerialized attention layers by kind
    with their o and lse bytes; None or no call: nothing to keep, `no_remat`), the train state's and the gradients' bytes
    a device, and the device's limit (`telemetry.device_memory.min_bytes_limit()`; None, a CPU: keep). Counted: state,
    gradients, every block's input, the kept o and lse, and a block's working set as the two constants above have it;
    `over_count` where that passes the limit. `allowed=False` is the preflight's verdict on a step that kept
    (`fell_back_in_preflight`). Returns `keep`, `verdict`, `layers` and `kept_bytes` (what keeping would hold, whatever
    the verdict), `counted_bytes`, `bytes_limit`."""
    if not flash_calls or not flash_calls["calls"]:
        return {"keep": False, "verdict": "no_remat", "layers": 0, "kept_bytes": 0, "counted_bytes": 0, "bytes_limit": bytes_limit}
    calls = flash_calls["calls"]
    kept = sum(call["layers"] * (call["o_bytes"] + call["lse_bytes"]) for call in calls)
    working = (KEEP_FLASH_WORKING_SETS * max(call["backward_bytes"] for call in calls)
               + KEEP_BLOCK_WORKING_INPUTS * flash_calls["block_input_bytes"])
    counted = state_bytes + gradient_bytes + flash_calls["blocks"] * flash_calls["block_input_bytes"] + kept + working
    verdict = "fell_back_in_preflight" if not allowed else "over_count" if bytes_limit is not None and counted > bytes_limit else "fits"
    return {"keep": verdict == "fits", "verdict": verdict, "layers": sum(call["layers"] for call in calls), "kept_bytes": kept,
            "counted_bytes": counted, "bytes_limit": bytes_limit}


class ActivationCheckpointing:
    """Registry-facing component: records the remat variant on the model's spec
    (applied when the jitted train step is built)."""

    @staticmethod
    def apply(model, variant: str | ActivationCheckpointingVariants, ac_freq: int = 1, save_list: tuple[str, ...] = ()):
        v = variant.value if isinstance(variant, ActivationCheckpointingVariants) else str(variant)
        mapping = {
            ActivationCheckpointingVariants.FULL_ACTIVATION_CHECKPOINTING.value: "full",
            ActivationCheckpointingVariants.SELECTIVE_LAYER_ACTIVATION_CHECKPOINTING.value: "selective_layer",
            ActivationCheckpointingVariants.SELECTIVE_OP_ACTIVATION_CHECKPOINTING.value: "selective_op",
        }
        if v not in mapping:
            raise ValueError(f"Unknown activation checkpointing variant {v!r}")
        return model.with_spec_updates(
            remat_variant=mapping[v], remat_freq=ac_freq, remat_save_list=tuple(save_list)
        )
