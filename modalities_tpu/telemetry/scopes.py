"""The vocabulary of scope names on device programs, written down once.

Every operation XLA emits carries, in its metadata, the `op_name` path JAX built while
tracing: `jit(train_step)/while/body/closed_call/transpose(jvp(GPT2Module))/while/body/
closed_call/blocks/block/mlp/W/dot_general`. A trace reader (`benchmark/xscope.py`,
`data analyze_perfscope`) reads an operation's pass and component off that path, so
the names on it are an interface. They are set with `jax.named_scope(<constant>)` at
the place the work happens; call sites import the constants below and spell no name
themselves. A scope is metadata: the optimized program is the same with and without it
(`tests/telemetry/test_scopes.py` pins that).

Pass is never set by hand. It is read from the transforms JAX writes itself:

    forward    under `jvp(` and not under `transpose(`
    backward   under `transpose(jvp(`; a `rematted_computation` below it counts as
               backward, being work the backward pass causes
    update     the train-step scopes of the first table

Train step (`training/train_step.py`), what autodiff does not mark:

    GRAD_ACCUMULATE   grad_accumulate   the zero accumulator, the sum and cast per microbatch, the division by acc_steps
    GRAD_NORM         grad_norm         the global norm of the gradients as reported
    OPTIMIZER         optimizer         `tx.update`: AdamW; a clip transform chained into `tx` shows as `optimizer/.../clip`
    CLIP              clip              the name optax's own `jit(clip)` gives; no scope of ours
    APPLY_UPDATES     apply_updates     `optax.apply_updates`, and ZeRO's all-gather of the new parameters
    ANOMALY_SELECT    anomaly_select    the skip of a non-finite step (`anomaly_policy`)
    STEP_METRICS      step_metrics      learning rate, flags and ballots of the metrics dict
    HEAD_LOSS         head_loss         the head projection with its loss, chunked, fused or dense:
                                        reads `jvp(head_loss)` / `transpose(jvp(head_loss))`

Model (`models/gpt2/gpt2_model.py`), beside the names Flax gives its modules:

    WTE               wte               the embedding lookup (and the tied head's use of the table)
    ROPE              rope              the qkv transforms
    ATTN_CORE         attn_core         the attention implementation's call: kernel, padding, reshapes, masks,
                                        the KV cache's write, the paged gather and scatter
    RESIDUAL          residual          the two adds of a block
    LAYER_CARRY       layer_carry       what the layer scan stacks or slices outside a block; where no scope
                                        reaches (the scan's own body), readers take the path
                                        `GPT2Module)/while/body` without `blocks/` for the same bucket

State-space mixer (`models/gpt2/ssm.py`), under the module name `ssm` that Flax gives it in a block's mixer seat:

    SSM_CONV          conv              the causal depthwise convolution (the name of its module)
    SSM_SCAN          scan              the recurrence (`ops/selective_scan.py`: on a TPU its two Pallas kernels) and its backward pass, nothing else
    SSM_GATE          gate              the skip `D * x` and the gate `silu(z)` on the scan's output

Expert layer (`models/gpt2/moe.py`, `ops/expert_dispatch.py`), under the module name `moe` that Flax gives it in a block's `MLP` seat:

    MOE_ROUTER        router            scores, choice and weights, float32 (the name of its module)
    MOE_DISPATCH      dispatch          the sort of the pairs by expert, the group sizes and tables (the `slabs` form's by block and expert too), each tile's gather of its tokens
    MOE_EXPERTS       experts           the grouped products of the held experts, both passes (and the module that holds their three stacks)
    MOE_SHARED        shared            the shared expert, a dense SwiGLU on every token (the name of its module)
    MOE_COMBINE       combine           the rows weighed and summed by token (the kernel `moe_combine` over slabs of rows, or k gathers:
                                        `ops/expert_dispatch.combine_plan`), its transpose in the tiles, the sum with the shared expert

A stack of window and global attention layers (`layer_types`, PR 38; `models/gpt2/gpt2_model.py`), round a block's `attn` module:

    ATTN_WINDOW       window            a `sliding_attention` layer's attention: `block/window/attn/{q_attn,...,rope,attn_core}`;
                                        `attn/rope` holds this kind's tables, `attn/attn_core` the windowed kernels
                                        (`flash_attention_window_{fwd,bwd}`)
    ATTN_GLOBAL       global            a `full_attention` layer's: `block/global/attn/...` (YaRN's tables under `attn/rope` where
                                        `rope_parameters` asks for them); a model without `layer_types` has neither name

Compressed convolutional attention (`cca_config`, a `hybrid` layer's mixer; `models/gpt2/cca.py`), under the module name `cca`
in a block's mixer seat; `cca/rope` (the rotary on the rotated part of a head) and `cca/attn_core` (the flash kernels) are the names above:

    CCA_LATENT        latent            the projections into the latent: q, k and the two halves of v (`cca/latent/{q_attn,k_attn,v_attn,v_attn_prev}`)
    CCA_CONV          conv              both convolutions over the sequence on q and k together: the depthwise one's shifted multiplies,
                                        the grouped one's head-batched products on the array and its shift
    CCA_QK_MEAN       qk_mean           the mean of the two pre-convolution latents, a query head's and its key head's, added to q and k
    CCA_QK_NORM       qk_norm           the L2 norm of every head of q and k, float32, and the learned key temperature
    CCA_VALUE_SHIFT   value_shift       the half of the value heads that is read off the previous position
    CCA_OUT           out               the output projection back to the residual's width (`cca/out/c_proj`)

The gated delta rule's mixer (`gdn_config`, a `linear_attention` layer's; `models/gpt2/gdn.py`, `ops/gated_delta_rule.py`), under the
module name `gdn` in a block's mixer seat:

    GDN_IN_PROJ       in_proj           the two projections of the block's normed input: q, k, v and z together (`gdn/in_proj/qkvz`), b and a (`gdn/in_proj/ba`)
    GDN_CONV          conv              the causal depthwise convolution over q, k and v together, and its SiLU
    GDN_GATES         gates             `beta = sigmoid(b)` and the log of the decay `g = -exp(A_log) softplus(a + dt_bias)`, float32
    GDN_QK_NORM       qk_norm           the L2 norm of every head of q and k, float32, and q's `1 / sqrt(d_k)`
    GDN_RULE          rule              the chunked rule; under it:
    GDN_INTRA         intra             what a chunk needs but the state, for all chunks at once: `L`, `T = (I + L)^-1`, `U`, `W`, the lower products
    GDN_STATE         state             the walk over the chunks that carries the `[d_k, d_v]` state a head (on a TPU the kernels `gated_delta_state_fwd` / `_bwd`, else a scan), and the carry from group to group
    GDN_GROUP         group             in the rule's backward alone: one group of chunks computed again from the state that came into it, `intra` and `state` inside it (`.../state/while/body/jvp(group)/intra/...`)
    GDN_OUT_NORM      out_norm          the RMS norm a head of the rule's output, times `silu(z)` (the name of the norm's module)
    GDN_OUT           out               the output projection back to the residual's width (`gdn/out/out_proj`)

The Mamba-2 mixer (`ssd_config`, a `mamba` layer's; `models/gpt2/ssd.py`, `ops/ssd.py`), under the module name `ssd` in a block's
mixer seat:

    SSD_IN_PROJ       in_proj           the one projection of the block's normed input: z, x, B, C and dt together (`ssd/in_proj/in_proj`)
    SSD_CONV          conv              the causal depthwise convolution over x, B and C together, its bias and its SiLU
    SSD_SCAN          scan              the chunked (state-space dual) form, with `dt`'s softplus and the log decays before it; under it:
    SSD_INTRA         intra             inside a chunk, for all chunks at once: the sums of the log decays, `L`, `C B^T`, `Y_in`
    SSD_STATE         state             the chunks' own states `S_c`, the pass over the chunks that carries `[heads, d_head, d_state]`, `Y_off`
    SSD_GATE          gate              the skip `D x`, `silu(z)` and the RMS norm over the inner width held, after the gate
    SSD_OUT_PROJ      out_proj          the output projection back to the residual's width (`ssd/out_proj/out_proj`)

The four multipliers (`embedding_multiplier`, `residual_multiplier`, `attention_multiplier`, `logits_scaling`) ride in the scopes that were
there: the table's output in `wte`, a branch's factor in `residual`, the scores' in the attention's own, the logits' on the normed hidden
state before the head.

Attention with an output gate (`attn_output_gate`): `attn/gate` holds the sigmoid of the gate half of `q_attn` times the attention's output.
An expert layer whose shared expert is gated (`shared_expert_gate`): `moe/shared_gate` holds the `[d, 1]` product, its sigmoid and the multiply.

The router of kind `mlp` (`moe_config.router: mlp`; `models/gpt2/moe.py`), inside `moe/router`:

    ROUTER_DOWN       down              the projection of the layer's input to the router's state (the name of its module)
    ROUTER_EDA        eda               the previous layer's state added in, under the learned gate
    ROUTER_MLP        mlp               the state's norm, the two hidden layers and the output columns (the skip column among them)

A block with `scale_residual_merge` holds the modules `attn_merge` and `ffn_merge` (four `[n_embd]` leaves each); their arithmetic is under `residual`.

Looped decoder (`loop_config`: the stack walked several times over one set of weights; `models/gpt2/gpt2_model.py`, `training/train_step.py`):

    LOOP              loop              round the walks: the carry between walks, every walk's exit stacked, and in the backward
                                        the sum of each shared weight's gradient over the walks (autodiff's form: a whole stack
                                        added a walk; under full remat the hand-written rule adds a layer's slice where it
                                        stands, inside `layer_carry`, and recomputes under `jvp(rematted_computation)/`);
                                        blocks, the layer scan (`layer_carry`), the final norm and the gate name themselves inside it
    EXIT_GATE         exit_gate         the gate read off every walk's exit, float32 (the name of its module)
    EXIT_LOSS         exit_loss         inside `head_loss`: the exit distribution from the gates, the cross entropies weighed by
                                        it, the entropy term, and what the step counts of them

A block with sandwich norms (`post_attention_norm_config`, `post_ffn_norm_config`) holds the modules
`post_attention_norm` and `post_ffn_norm` beside `attention_norm` and `ffn_norm`.

Latent attention (`models/gpt2/mla.py`) sits in the mixer seat under `attn` as the other
attention does, with `attn/rope` and `attn/attn_core`; its projections keep Flax's names.

Module names Flax gives, part of the vocabulary as they are (`flax_profile` puts them
on the stack): `GPT2Module`, `blocks/block` (`h_<i>` when the layers are not scanned),
`attn/{q_attn,k_attn,v_attn,c_proj}`, `mlp/{W,V,W_2,c_fc,c_proj}`, `attention_norm`,
`ffn_norm`, `lm_head_norm`, `lm_head`; a model whose layers are of more than one kind
puts `run_<i>` before `blocks/block` (one scan a run of equal layers), and the state-space
mixer's projections are `ssm/{in_proj,x_proj,dt_proj,out_proj}` with `ssm/{dt_norm,b_norm,c_norm}`;
latent attention's are `attn/{q_proj,kv_a_proj,kv_a_norm,kv_b_proj,c_proj}`, the expert layer's
`moe/router`, `moe/experts`, `moe/shared/{W,V,W_2}`. Kernels keep the `name=` of their Pallas call:
`flash_attention_{fwd,bwd}` (`flash_attention_bwd_{dq,dkv}` in ring attention's backward and where a row's dq does not
fit VMEM: `ops/pallas/flash_attention.backward_plan`), `fused_ce_{fwd,bwd_dw}` (`fused_ce_eval` where nobody differentiates the call),
`fused_rmsnorm_{fwd,bwd}`, `selective_scan_{fwd,bwd}` (the recurrence on a TPU, under
`ssm/scan`); the instruction of a call is named by it, and metrics select by that name.

Host spans (`telemetry/spans.py`) are a second vocabulary, on the host's rows of the
same trace: `data_wait`, `train_step`, `metrics_fetch`, `publish`, `serve/admission`,
`serve/prefill`, `serve/decode`.
"""

GRAD_ACCUMULATE = "grad_accumulate"
GRAD_NORM = "grad_norm"
CLIP = "clip"
OPTIMIZER = "optimizer"
APPLY_UPDATES = "apply_updates"
ANOMALY_SELECT = "anomaly_select"
STEP_METRICS = "step_metrics"
HEAD_LOSS = "head_loss"
LM_HEAD = "lm_head"  # the name Flax gives the untied head's module; the tied head's einsum is set under it too

WTE = "wte"
ROPE = "rope"
ATTN_CORE = "attn_core"
RESIDUAL = "residual"
LAYER_CARRY = "layer_carry"

SSM = "ssm"  # the mixer's module name in the block's seat
SSM_CONV = "conv"
SSM_SCAN = "scan"
SSM_GATE = "gate"

ATTN_WINDOW = "window"  # round the attention of a window layer, and of a global layer beside one
ATTN_GLOBAL = "global"

LOOP = "loop"  # a looped model's walks
EXIT_GATE = "exit_gate"  # and the name of the gate's module
EXIT_LOSS = "exit_loss"
POST_ATTENTION_NORM = "post_attention_norm"  # the names of a sandwich block's two further norm modules
POST_FFN_NORM = "post_ffn_norm"

MOE = "moe"  # the expert layer's module name in the block's `MLP` seat
MOE_ROUTER = "router"
MOE_DISPATCH = "dispatch"
MOE_EXPERTS = "experts"
MOE_SHARED = "shared"
MOE_COMBINE = "combine"

CCA = "cca"  # the mixer's module name in the block's seat (a `hybrid` layer)
CCA_LATENT = "latent"
CCA_CONV = "conv"
CCA_QK_MEAN = "qk_mean"
CCA_QK_NORM = "qk_norm"
CCA_VALUE_SHIFT = "value_shift"
CCA_OUT = "out"
ROUTER_DOWN = "down"  # inside `moe/router`, a router of kind `mlp`
ROUTER_EDA = "eda"
ROUTER_MLP = "mlp"

GDN = "gdn"  # the mixer's module name in the block's seat (a `linear_attention` layer)
GDN_IN_PROJ = "in_proj"
GDN_CONV = "conv"
GDN_GATES = "gates"
GDN_QK_NORM = "qk_norm"
GDN_RULE = "rule"
GDN_INTRA = "intra"
GDN_STATE = "state"
GDN_GROUP = "group"
GDN_OUT_NORM = "out_norm"
GDN_OUT = "out"
SSD = "ssd"  # the mixer's module name in the block's seat (a `mamba` layer)
SSD_IN_PROJ = "in_proj"
SSD_CONV = "conv"
SSD_SCAN = "scan"
SSD_INTRA = "intra"
SSD_STATE = "state"
SSD_GATE = "gate"
SSD_OUT_PROJ = "out_proj"
ATTN_GATE = "gate"  # inside `attn`, where the attention's output is gated (`attn_output_gate`)
MOE_SHARED_GATE = "shared_gate"  # inside `moe`, where the shared expert is gated (`shared_expert_gate`)

UPDATE_SCOPES = (GRAD_ACCUMULATE, GRAD_NORM, CLIP, OPTIMIZER, APPLY_UPDATES, ANOMALY_SELECT, STEP_METRICS)
MODEL_SCOPES = (WTE, ROPE, ATTN_CORE, RESIDUAL, LAYER_CARRY)
SSM_SCOPES = (SSM_CONV, SSM_SCAN, SSM_GATE)  # on the step only where a layer holds the state-space mixer

LOOP_SCOPES = (LOOP, EXIT_GATE, EXIT_LOSS)  # on the step only where the stack is walked several times (`loop_config`)

WINDOW_SCOPES = (ATTN_WINDOW, ATTN_GLOBAL)  # on the step only where the stack holds window layers (`layer_types`)

MOE_SCOPES = (MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS, MOE_SHARED, MOE_COMBINE)  # on the step only where a layer holds experts

CCA_SCOPES = (CCA_LATENT, CCA_CONV, CCA_QK_MEAN, CCA_QK_NORM, CCA_VALUE_SHIFT, CCA_OUT)  # on the step only where a layer's mixer is `cca`
ROUTER_MLP_SCOPES = (ROUTER_DOWN, ROUTER_EDA, ROUTER_MLP)  # on the step only where the router is an MLP over a carried state
GDN_SCOPES = (GDN_IN_PROJ, GDN_CONV, GDN_GATES, GDN_QK_NORM, GDN_RULE, GDN_INTRA, GDN_STATE, GDN_GROUP, GDN_OUT_NORM, GDN_OUT)  # on the step only where a layer's mixer is `gdn`
SSD_SCOPES = (SSD_IN_PROJ, SSD_CONV, SSD_SCAN, SSD_INTRA, SSD_STATE, SSD_GATE, SSD_OUT_PROJ)  # on the step only where a layer's mixer is `ssd`

# what a path holds beside scopes: the jit wrapper, the plumbing of loops, calls and branches
_PLUMBING = frozenset(("while", "body", "cond", "closed_call", "checkpoint"))


def scope_path(op_name):
    """An `op_name` cut down to its scopes, for a table a person reads: without the
    primitive that ends it, the `jit(...)` that opens it and the plumbing of loops,
    calls and branches between. `None` or a bare primitive gives `(no scope)`."""
    if not op_name or "/" not in op_name:
        return "(no scope)"
    kept = [part for part in op_name.split("/")[:-1]
            if part not in _PLUMBING and not part.startswith("branch_") and not (part.startswith("jit(") and part.endswith(")"))]
    return "/".join(kept) or "(no scope)"
