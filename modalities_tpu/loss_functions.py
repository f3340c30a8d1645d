"""Loss functions (reference: src/modalities/loss_functions.py:10-167).

Losses are pure jax functions over an InferenceResultBatch-shaped dict pair; they run
*inside* the jitted train step, so reduction across the mesh is a plain mean that
GSPMD turns into the right collectives.
"""

from __future__ import annotations

from abc import ABC, abstractmethod

import jax
import jax.numpy as jnp
import optax


class Loss(ABC):
    def __init__(self, tag: str = "loss"):
        self._tag = tag

    @property
    def tag(self) -> str:
        return self._tag

    @abstractmethod
    def __call__(self, predictions: dict, targets: dict):
        """Compute the scalar loss from prediction/target dicts of jax arrays."""


class CLMCrossEntropyLoss(Loss):
    """Mean causal-LM cross entropy over non-ignored target positions
    (reference: loss_functions.py:27-87)."""

    def __init__(self, target_key: str, prediction_key: str, tag: str = "CLMCrossEntropyLoss",
                 ignore_index: int = -100):
        super().__init__(tag)
        self.target_key = target_key
        self.prediction_key = prediction_key
        self.ignore_index = ignore_index

    def row_losses(self, logits, labels):
        """(per-token CE in the shape of `labels`, 0 where ignored; the float32 mask of what counts)."""
        mask = (labels != self.ignore_index).astype(jnp.float32)
        safe_labels = jnp.where(labels == self.ignore_index, 0, labels)
        token_losses = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), safe_labels
        )
        return token_losses * mask, mask

    def sum_and_count(self, logits, labels):
        """(sum of per-token CE over non-ignored positions, their count) — the
        accumulation form used by the chunked head+loss path and the pipeline
        executor's token-weighted mean."""
        rows, mask = self.row_losses(logits, labels)
        return rows.sum(), mask.sum()

    def fused_sum_and_count(self, hidden, head_weight, labels, interpret: bool = False):
        """`sum_and_count` without ever materializing logits: the Pallas
        vocab-streaming fused-CE kernel consumes the pre-head hidden states
        `[..., E]` and the head weight `[V, E]` directly (ops/cross_entropy.py
        dispatch; the chunked scan in train_step stays the fallback tier)."""
        from modalities_tpu.ops.cross_entropy import fused_ce_sum_and_count

        return fused_ce_sum_and_count(
            hidden, head_weight, labels, ignore_index=self.ignore_index, interpret=interpret
        )

    def __call__(self, predictions: dict, targets: dict):
        total, count = self.sum_and_count(
            predictions[self.prediction_key], targets[self.target_key]
        )
        return total / jnp.maximum(count, 1.0)


def exit_counter_names(walks: int) -> tuple[str, ...]:
    """What `LoopedExitLoss.exit_loss` counts for a model of `walks` exits, as the step publishes them."""
    return tuple(f"loop_exit_ce_{t}" for t in range(1, walks + 1)) + ("loop_expected_exit", "loop_gate_entropy")


class LoopedExitLoss(CLMCrossEntropyLoss):
    """Training loss of a looped decoder with an exit gate (`loop_config`; Ouro, arXiv 2510.25741): per
    token the cross entropy of every exit weighed by the gate's exit distribution, minus `beta` times
    that distribution's entropy, then the mean over the tokens that count:

        p_1 = g_1,  p_t = g_t prod_{j<t} (1 - g_j)  (t < T),  p_T = prod_{j<T} (1 - g_j)
        loss = mean_i [ sum_t p_i(t) CE_i(t) - beta H(p_i) ],  H(p) = -sum_t p(t) log p(t)

    with the gradient through both `p` and the cross entropies. The train step hands `exit_loss` every
    exit's per-row cross entropy (one fused call over `T x B x S` rows, or the chunked scan) and the
    gate logits; called on a predictions dict (an evaluation: the last exit's logits) it is the plain
    mean cross entropy, which is what a model that runs every walk reports."""

    def __init__(self, target_key: str, prediction_key: str, tag: str = "LoopedExitLoss", ignore_index: int = -100):
        super().__init__(target_key, prediction_key, tag, ignore_index)

    def fused_row_losses(self, hidden, head_weight, labels, interpret: bool = False):
        """`row_losses(...)[0]` without the logits: the per-row entry of the fused-CE kernels (ops/cross_entropy.py)."""
        from modalities_tpu.ops.cross_entropy import fused_ce_rows

        return fused_ce_rows(hidden, head_weight, labels, ignore_index=self.ignore_index, interpret=interpret)

    def exit_loss(self, row_ce, gate_logits, labels, beta: float):
        """`row_ce` and `gate_logits` `[T, B, S]` (exit t's per-row cross entropy, 0 where ignored, and gate
        logit), `labels` `[B, S]` -> (loss, what the step counts: `exit_counter_names(T)`). float32; the
        distribution from log-sigmoids, so a saturated gate gives a small probability and not a NaN."""
        mask = (labels != self.ignore_index).astype(jnp.float32)
        count = jnp.maximum(mask.sum(), 1.0)
        gate_logits = gate_logits.astype(jnp.float32)
        walks = row_ce.shape[0]
        log_stay = jax.nn.log_sigmoid(-gate_logits)  # log (1 - g_t)
        log_reach = jnp.cumsum(log_stay, axis=0) - log_stay  # log prod_{j<t} (1 - g_j)
        log_p = jnp.concatenate([log_reach[:-1] + jax.nn.log_sigmoid(gate_logits[:-1]), log_reach[-1:]], axis=0)
        p = jnp.exp(log_p)
        entropy = -(p * log_p).sum(axis=0)
        per_token = (p * row_ce).sum(axis=0) - beta * entropy
        mean = lambda rows: (rows * mask).sum() / count  # noqa: E731
        steps = jnp.arange(1, walks + 1, dtype=jnp.float32)[:, None, None]
        values = [mean(row_ce[t]) for t in range(walks)] + [mean((p * steps).sum(axis=0)), mean(entropy)]
        return mean(per_token), dict(zip(exit_counter_names(walks), values))


class NCELoss(Loss):
    """Symmetric InfoNCE contrastive loss for CoCa (reference: loss_functions.py:90-167)."""

    def __init__(
        self,
        prediction_key1: str,
        prediction_key2: str,
        is_asymmetric: bool = True,
        temperature: float = 1.0,
        tag: str = "NCELoss",
    ):
        super().__init__(tag)
        self.prediction_key1 = prediction_key1
        self.prediction_key2 = prediction_key2
        self.is_asymmetric = is_asymmetric
        self.temperature = temperature

    def __call__(self, predictions: dict, targets: dict):
        e1 = predictions[self.prediction_key1].astype(jnp.float32)
        e2 = predictions[self.prediction_key2].astype(jnp.float32)
        e1 = e1 / jnp.maximum(jnp.linalg.norm(e1, axis=-1, keepdims=True), 1e-8)
        e2 = e2 / jnp.maximum(jnp.linalg.norm(e2, axis=-1, keepdims=True), 1e-8)
        sim = e1 @ e2.T / self.temperature
        n = sim.shape[0]
        labels = jnp.arange(n)
        loss_12 = optax.softmax_cross_entropy_with_integer_labels(sim, labels).mean()
        if self.is_asymmetric:
            return loss_12
        loss_21 = optax.softmax_cross_entropy_with_integer_labels(sim.T, labels).mean()
        return 0.5 * (loss_12 + loss_21)
