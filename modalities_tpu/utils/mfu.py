"""Model FLOPs utilization (reference: src/modalities/utils/mfu.py:150-197).

Same flops-per-token formula (6N + 12*L*s*h, reference :178-180); the GPU peak-flops
table (:17) becomes a TPU-generation table keyed off the device kind.
"""

from __future__ import annotations

from abc import ABC, abstractmethod
from typing import Optional

# bf16 peak FLOP/s per chip by TPU generation
TPU_PEAK_FLOPS = {
    "v6e": 918e12,
    "v6": 918e12,
    "v5p": 459e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v4": 275e12,
}
# So that a calculator can be built where the tests run. Nothing scored against
# it is a device metric.
_CPU_NOMINAL_PEAK = 1e12


def get_peak_flops(device_kind: Optional[str] = None) -> float:
    """Peak of the given (default: the first attached) device kind. An accelerator
    with no row raises: a utilization against another chip's peak is a wrong number."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    kind = device_kind.lower()
    if "cpu" in kind:
        return _CPU_NOMINAL_PEAK
    for key, val in TPU_PEAK_FLOPS.items():
        if key in kind:
            return val
    raise ValueError(
        f"Unknown accelerator kind {device_kind!r}: no entry in TPU_PEAK_FLOPS "
        f"(known: {sorted(TPU_PEAK_FLOPS)}). Add its bf16 peak with the source."
    )


class MFUCalculatorIF(ABC):
    @abstractmethod
    def compute(self, tokens_per_second: float) -> float: ...


class GPT2MFUCalculator(MFUCalculatorIF):
    """MFU = tokens/s * (6N + 12*L*s*h) / (world * peak) (reference :150-197), with L the
    layers that hold attention: all of them in the dense decoder; in a model whose layers
    are of two kinds (`attn_layer_period`), read off the wrapped model's spec, those that
    are not state-space layers (whose scan is elementwise work and adds no `s*h` term).

    A model with expert layers or latent attention (`moe_config`, `mla_config`) is counted
    by what a token passes, never by the dense formula: N without the routed experts'
    stacks plus `num_experts_per_tok` experts a token an expert layer (every chosen expert:
    where only a share of the experts is held, `experts_held / n_routed_experts` of them on
    average), and `6 * s * H * (qk_head_dim + v_head_dim)` a layer for the attention's two
    products at their two head sizes (full, as the dense formula counts attention).

    A layer under a window (`layer_types`: `sliding_attention`) sees `sliding_window` positions and not the
    sequence: it adds `6 * min(2 W, s) * H * 2 * head_dim`, and `head_dim` is the config's own where it gives one.

    A layer of compressed convolutional attention (`cca_config`) is counted as it is held: its projections into and out of
    the latent and the grouped convolution's two products a head are parameters a token multiplies (in `6N`), the causal
    scores at `n_head_q` heads of `head_dim` the `12 L s h` term at that width; the MLP router's matrices are in `6N` too, and
    of the held experts a token passes `num_experts_per_tok * experts_held / columns`, the router's skip column among the
    columns (a token that picks it passes none). The tied head is `6 E V`, the table's own count.

    A layer of the gated delta rule (`gdn_config`) is counted as it is held: its three projections are parameters a token
    multiplies (in `6N`), its convolution's taps too (a multiply-add a tap and a channel), and the chunked rule's products a
    chunk and a value head (`k k^T`, `q k^T`, `T` against its two right sides, the three products with the state and the lower
    product; the nilpotent series that builds `T` beside them) come to `gdn_rule_flops_per_token` forward, times 3 for a step.
    Attention whose output is gated holds the gate's half of `q_attn` in `6N`; its scores are the `12 L s h` term at
    `n_head_q * head_dim`. The shared expert, its gate and the router are in `6N` whole; of the held experts a token passes
    `num_experts_per_tok * experts_held / columns`.

    A Mamba-2 layer (`ssd_config`) is counted as it is held: its two projections and its taps are parameters a token multiplies
    (in `6N`), and the chunked form's products a chunk (`C B^T` once, and a head's `(L o C B^T) X`, its own state `X^T B` and
    `C H`) come to `ssd_scan_flops_per_token` forward, times 3 for a step.

    A looped model (`loop_config`) uses a parameter once for every walk, and `6N` would count it
    once: its required operations are `6 x a layer's kernels x L x T` + `6 x T x L x s x h` (the
    causal half of attention, a layer application) + `6 x T x E x V` (the head, once an exit) a
    token, and the embedding, a gather, none."""

    def __init__(
        self,
        n_layer: int,
        sequence_length: int,
        n_embd: int,
        world_size: int,
        num_parameters: Optional[int] = None,
        model_parts=None,
        device_mesh=None,
        wrapped_model=None,
    ):
        self.n_layer = n_layer
        self.sequence_length = sequence_length
        self.n_embd = n_embd
        self.world_size = world_size
        if num_parameters is None and model_parts is not None:
            num_parameters = _count_params(model_parts)
        if num_parameters is None and wrapped_model is not None:
            num_parameters = _count_params(wrapped_model)
        self.num_parameters = num_parameters or 0
        spec = getattr(model_parts if model_parts is not None else wrapped_model, "config_spec", None)
        kinds = getattr(spec, "layer_kinds", ())
        self.n_attention_layer = kinds.count("attn") + kinds.count("cca") if kinds else n_layer
        self.active_parameters = self.num_parameters
        self.attention_width = 2 * n_embd  # q k^T and p v, each n_head * head_dim = n_embd wide
        if getattr(spec, "head_dim_key", None) is not None:
            self.attention_width = 2 * spec.n_head_q * spec.head_dim
        # a window layer's positions, in the formula's own convention (the `s` of `12 L s h` is twice what a causal row
        # sees on average): twice the window, never more than the sequence
        window = getattr(spec, "sliding_window", None)
        self.window_positions = kinds.count("swa") * min(2 * window, sequence_length) if window else 0
        moe, mla = getattr(spec, "moe", None), getattr(spec, "mla", None)
        if moe is not None:
            expert = 3 * n_embd * moe.moe_intermediate_size
            expert_layers = spec.ffn_kinds.count("moe")
            chosen_here = moe.num_experts_per_tok * moe.experts_held / moe.router_width
            self.active_parameters = self.num_parameters - expert_layers * (moe.experts_held - chosen_here) * expert
        if mla is not None:
            self.attention_width = spec.n_head_q * (mla.qk_head_dim + mla.v_head_dim)
        gdn = getattr(spec, "gdn", None)
        self.rule_flops_per_token = kinds.count("gdn") * gdn_rule_flops_per_token(gdn) if gdn is not None else 0.0
        ssd = getattr(spec, "ssd", None)
        if ssd is not None:
            self.rule_flops_per_token += kinds.count("ssd") * ssd_scan_flops_per_token(ssd)
        self.looped_flops_per_token = None
        loop = getattr(spec, "loop", None)
        if loop is not None:
            mlp = (3 * spec.swiglu_hidden if "swiglu" in spec.activation else 2 * spec.ffn_hidden) * n_embd
            kernels = 2 * n_embd * spec.head_dim * (spec.n_head_q + spec.n_head_kv) + mlp
            walks = loop.total_ut_steps
            self.looped_flops_per_token = 6 * walks * (n_layer * (kernels + sequence_length * n_embd) + n_embd * spec.vocab_size)
        self._peak = get_peak_flops()

    def compute(self, tokens_per_second: float) -> float:
        flops_per_token = self.looped_flops_per_token or (
            6 * self.active_parameters + 3 * self.rule_flops_per_token
            + 6 * (self.n_attention_layer * self.sequence_length + self.window_positions) * self.attention_width)
        return tokens_per_second * flops_per_token / (self.world_size * self._peak)


def gdn_rule_flops_per_token(gdn, chunk: int = 64) -> float:
    """Forward operations a token of one layer's chunked gated delta rule (`ops/gated_delta_rule.py`), beside its projections
    and taps (which are parameters): a chunk of `C` positions and a value head take `k k^T` and `q k^T` (`2 C^2 d_k` each, once a
    key head: divided by the heads that share it), the nilpotent series for `T` (`2 log2(C) - 1` products of `[C, C]`), `T`
    against `[beta v | beta k exp(G)]` (`2 C^2 (d_k + d_v)`), `W S`, `q S` and `k^T V'` (`2 C d_k d_v` each) and the lower
    product (`2 C^2 d_v`); divided by `C` and summed over the value heads."""
    import math

    c, dk, dv, share = chunk, gdn.key_dim, gdn.value_dim, gdn.value_heads // gdn.key_heads
    a_head = (2 * 2 * c * c * dk / share + (2 * int(math.log2(c)) - 1) * 2 * c ** 3 + 2 * c * c * (dk + dv)
              + 3 * 2 * c * dk * dv + 2 * c * c * dv)
    return gdn.value_heads * a_head / c


def ssd_scan_flops_per_token(ssd) -> float:
    """Forward operations a token of one layer's chunked Mamba-2 recurrence (`ops/ssd.py`), beside its projections and taps
    (which are parameters): a chunk of `Q` positions takes `C B^T` once (`2 Q^2 N`), and a held head `(L o C B^T) X`
    (`2 Q^2 P`), its own state `X^T B` and `C H` against the state that came in (`2 Q P N` each); divided by `Q`."""
    q, p, n = ssd.chunk, ssd.head_dim, ssd.state
    return (2 * q * q * n + ssd.heads_held * (2 * q * q * p + 2 * 2 * q * p * n)) / q


def _count_params(model) -> Optional[int]:
    """Count parameters of an NNModel without materializing them (eval_shape)."""
    try:
        import jax
        import numpy as np

        abstract = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
        return int(sum(np.prod(x.shape) for x in jax.tree.leaves(abstract)))
    except Exception:
        return None
