"""Fused cross-entropy dispatch: the Pallas vocab-streaming kernel (ops/pallas/fused_ce.py)
per shard of the rows.

Whether a train step takes it is `ops/tiers.py`'s one rule, asked where the step is built
(`training/train_step.py`: on a TPU, for a model with `lm_head_chunk_size` set; elsewhere
the chunked scan). A call that gets here runs the kernel, interpreted off a TPU.

Block sizes: the tuning table (`ops/pallas/autotune.blocks`), else the defaults below.
"""

from __future__ import annotations

from modalities_tpu.ops import tiers
from modalities_tpu.ops.pallas import autotune

DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_VOCAB = 512


def resolve_ce_blocks(rows: int, vocab: int, n_embd: int, dtype) -> tuple[int, int]:
    bucket = f"n{autotune.shape_bucket(rows)}_v{autotune.shape_bucket(vocab)}_e{autotune.shape_bucket(n_embd)}"
    return autotune.blocks("fused_ce", bucket, dtype, block_rows=DEFAULT_BLOCK_ROWS, block_vocab=DEFAULT_BLOCK_VOCAB)


def _row_layout(labels):
    """Logical axes of the rows: `[..., B, S]` split over batch and sequence (leading dims, a looped
    model's exits, stay whole); any other layout stays whole."""
    return (None,) * (labels.ndim - 2) + ("batch", "seq_sp") if labels.ndim >= 2 else (None,) * labels.ndim


def _dispatch(hidden, head_weight, labels, ignore_index: int, interpret: bool, rows_out: bool):
    import jax
    import numpy as np

    from modalities_tpu.ops.pallas.fused_ce import fused_ce_rows as pallas_rows
    from modalities_tpu.ops.pallas.fused_ce import fused_ce_sum_and_count as pallas_sum_and_count
    from modalities_tpu.parallel.sharding import per_shard

    rows = int(np.prod(hidden.shape[:-1])) if hidden.ndim > 1 else hidden.shape[0]
    block_rows, block_vocab = resolve_ce_blocks(rows, head_weight.shape[0], hidden.shape[-1], hidden.dtype)
    interpret = tiers.interpret(interpret)
    pallas_entry = pallas_rows if rows_out else pallas_sum_and_count

    def kernel(axes, hidden, head_weight, labels):
        out = pallas_entry(
            hidden, head_weight, labels,
            ignore_index=ignore_index, block_rows=block_rows, block_vocab=block_vocab, interpret=interpret,
        )
        return out if rows_out or not axes else jax.lax.psum(out, axes)

    row_axes = _row_layout(labels)
    return per_shard(kernel, (row_axes + (None,), (None, None), row_axes), row_axes if rows_out else ((), ()))(
        hidden, head_weight, labels
    )


def fused_ce_sum_and_count(hidden, head_weight, labels, *, ignore_index: int = -100, interpret: bool = False):
    """(total_loss, token_count) over hidden @ head_weight.T without the logits
    buffer. Drop-in for `loss_fn.sum_and_count(head_logits(...), labels)`.

    Whatever the kernel raises is raised, on a TPU as in interpret mode (tests):
    there is no dense tier behind it. Under a mesh the kernel runs per shard of
    the rows with the head gathered whole, and the two sums are added up over the
    axes the rows were split on (parallel/sharding.per_shard)."""
    return _dispatch(hidden, head_weight, labels, ignore_index, interpret, rows_out=False)


def fused_ce_rows(hidden, head_weight, labels, *, ignore_index: int = -100, interpret: bool = False):
    """The cross entropy of every row, float32 in the shape of `labels` (0 where the label is
    `ignore_index`): `fused_ce_sum_and_count` before its sum, for a loss that weighs each row
    (a looped model's `[T, B, S, E]` exits are `T x B x S` rows of ONE call against one head).
    Under a mesh each shard keeps its own rows: nothing is added up."""
    return _dispatch(hidden, head_weight, labels, ignore_index, interpret, rows_out=True)
