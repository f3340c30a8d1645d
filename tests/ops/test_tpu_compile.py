"""The main path's kernels, compiled at real widths by the TPU's own compiler for a
v5e that is described, not attached (the `on-chip-measurement` guide, section 2).

Interpret mode cannot see what Mosaic refuses: a block the (8, 128) tiling does
not accept, or more scoped VMEM than a kernel may use. Both got past every
interpret-mode test once (fused RMSNorm backward, fused CE backward at E 2560).
Nothing runs here and nothing is timed; a compile that passes is not a chip run.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from modalities_tpu.ops.embedding import embedding_lookup, grad_plan
from modalities_tpu.ops.pallas.flash_attention import (
    flash_bwd_dkv,
    flash_bwd_dq,
    flash_fwd_out_lse,
    pallas_flash_attention,
)
from modalities_tpu.ops.pallas.fused_ce import fused_ce_rows, fused_ce_sum_and_count
from modalities_tpu.ops.pallas.fused_rmsnorm import fused_rms_norm
from modalities_tpu.ops.pallas.gated_delta_state import plan_heads, walk
from modalities_tpu.ops.pallas.head_norm import KERNELS as HEAD_NORM_KERNELS, gated_head_rms_norm, head_l2_norm
from modalities_tpu.ops.pallas.moe_combine import moe_combine, pad_rows, vmem_bytes
from modalities_tpu.ops.pallas.quant_matmul import quant_matmul
from modalities_tpu.ops.pallas.selective_scan import pallas_selective_scan
from tests.telemetry.test_scopes import without_metadata

BF16, F32, VOCAB, SEQ = jnp.bfloat16, jnp.float32, 50304, 4096


def _table_blocks(head_dim: int, head_dim_v: int, kernel: str = "flash_attention") -> tuple[int, int]:
    """What tuning_tables/v5e.json gives the dispatcher at these widths, whatever the sequence."""
    from modalities_tpu.ops.pallas import autotune

    hit = autotune.lookup(kernel, f"d{head_dim}_dv{head_dim_v}", "bfloat16", device_kind="TPU v5 lite")
    return hit["block_q"], hit["block_k"]


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topology = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or one that cannot describe a v5e
        pytest.skip(f"TPU topology cannot be described: {e!r}")
    # a compile for a described chip is written to the persistent cache but cannot
    # be read back without one: keep the cache out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topology.devices
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


# tests/conftest.py makes the suite's CPU compiles cheap through XLA_FLAGS, which libtpu's compiler reads too. A compile for the
# described chip is the chip's compiler at XLA's own defaults: every one in this file (and `_compiled_cell_step`'s callers) is made here.
CHIP_DEFAULTS = {"xla_backend_optimization_level": 3, "xla_llvm_disable_expensive_passes": False}


def compiled_for_the_chip(lowered):
    return lowered.compile(compiler_options=CHIP_DEFAULTS)


FUSED = ("flash_attention_fwd", "flash_attention_bwd")  # PR 31: the backward is one kernel where a q head's dq row fits VMEM
TWO_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkv")


def _flash(heads_q, heads_kv, head_dim, seq=SEQ, kernels=FUSED):
    """Forward and backward at the table's 1024 x 1024: the dense cell's 32 q on 8 kv heads of 80 and the hybrid cell's
    20 on 1 of 128, both at 4096, take the fused backward; configs/config_long_context_32k.yaml's 12 on 4 of 128 at
    32,768 (a dq row of 16 MiB float32 and as much again for its output block) lands on the two kernels, as before PR 31."""
    def loss(q, k, v):
        out = pallas_flash_attention(q, k, v, block_q=1024, block_k=1024)
        return out.astype(F32).sum()

    q = ((1, seq, heads_q, head_dim), BF16)
    kv = ((1, seq, heads_kv, head_dim), BF16)
    return jax.grad(loss, argnums=(0, 1, 2)), (q, kv, kv), kernels


def _flash_at_the_tables_blocks(heads_q, heads_kv, head_dim, seq):
    """Forward and backward at the blocks `tuning_tables/v5e.json` gives a head of this width (PR 44: heads of 256 have a bucket of
    their own, `d256_dv256`): at one row of 16,384 a q head's resident dq is 32 MiB, the fused backward fits its VMEM budget at the
    backward entry's 512 x 512 (counted 42 MiB of 48) and would not at the forward's 1024 x 1024 (60), where the two kernels' `bwd_dkv`
    asks 18.65 MiB of Mosaic's 16."""
    from modalities_tpu.ops.pallas.flash_attention import backward_plan

    forward, backward = _table_blocks(head_dim, head_dim), _table_blocks(head_dim, head_dim, "flash_attention_bwd")
    assert backward_plan(seq, *backward, head_dim, head_dim, BF16)["backward"] == "fused"
    assert backward_plan(seq, *forward, head_dim, head_dim, BF16)["backward"] == "two_kernels"

    def loss(q, k, v):
        return pallas_flash_attention(q, k, v, block_q=forward[0], block_k=forward[1], bwd_blocks=backward).astype(F32).sum()

    q, kv = ((1, seq, heads_q, head_dim), BF16), ((1, seq, heads_kv, head_dim), BF16)
    return jax.grad(loss, argnums=(0, 1, 2)), (q, kv, kv), FUSED


WINDOW_FUSED = ("flash_attention_window_fwd", "flash_attention_window_bwd")


def _flash_window(block_q, block_k, window=1024, seq=4 * SEQ):
    """The window layers' call of benchmark/configs/mellum2-12b-a2p5b-d12 (PR 38): one row of 16,384, 32 q on 4 kv heads of
    128 under a window of 1024, forward and the fused backward (a head's dq row of 16 MiB float32 resident: 41.9 MiB counted
    of the kernel's 48), under the windowed calls' own labels; at the three block pairs the builder read on the chip."""
    def loss(q, k, v):
        return pallas_flash_attention(q, k, v, block_q=block_q, block_k=block_k, window=window).astype(F32).sum()

    q, kv = ((1, seq, 32, 128), BF16), ((1, seq, 4, 128), BF16)
    return jax.grad(loss, argnums=(0, 1, 2)), (q, kv, kv), WINDOW_FUSED


def _flash_two_widths(batch, seq, heads, head_dim, head_dim_v):
    """Latent attention's kernels: q and k wider than v (benchmark/configs/kanana2-30b-a3b-d9: 2 x 8192 x 32 heads of
    192 / 128), at the blocks the tuning table's own bucket gives them (1024 x 1024 asks 17.27 MiB for `bwd_dq`); the
    fused backward holds a head's dq row (8 MiB float32 at 8192 x 192) under its own, raised VMEM limit, at its own
    entry's blocks (1024 x 1024)."""
    block_q, block_k = _table_blocks(head_dim, head_dim_v)
    bwd_blocks = _table_blocks(head_dim, head_dim_v, "flash_attention_bwd")

    def loss(q, k, v):
        return pallas_flash_attention(q, k, v, block_q=block_q, block_k=block_k, bwd_blocks=bwd_blocks).astype(F32).sum()

    wide, narrow = ((batch, seq, heads, head_dim), BF16), ((batch, seq, heads, head_dim_v), BF16)
    return jax.grad(loss, argnums=(0, 1, 2)), (wide, wide, narrow), FUSED


def _ring_hop_not_causal():
    """An off-diagonal hop of ring attention (parallel/ring_attention.py) at
    configs/config_7b_warmstart_32k.yaml's per-device shape: 32,768 over cp 4, 32 q and
    8 kv heads of 128 over tp 8; the three kernels bare, on the whole rectangle."""
    kw = dict(causal=False, sm_scale=128**-0.5, block_q=1024, block_k=1024, interpret=False)

    def hop(q, k, v, do, delta):
        out, lse = flash_fwd_out_lse(q, k, v, **kw)
        return out, flash_bwd_dq(q, k, v, do, lse, delta, **kw), flash_bwd_dkv(q, k, v, do, lse, delta, **kw)

    q, kv = ((1, 4, 8192, 128), BF16), ((1, 1, 8192, 128), BF16)
    return hop, (q, kv, kv, q, ((1, 4, 1, 8192), F32)), 3


def _fused_ce(n_embd, rows, vocab=VOCAB):
    """Forward with d_hidden carried in its running sums, and the head's backward: the dense
    cell's 8,192 rows (and 16,384, a microbatch of 4) against 50,304, the hybrid cell's 4,096
    against the 32,768 rows of its tied table, the expert cell's 16,384 at width 2048 against 16,128."""
    def loss(hidden, head, labels):
        # the blocks tuning_tables/v5e.json ships; the kernel steps them down to VMEM
        total, count = fused_ce_sum_and_count(hidden, head, labels, block_rows=256, block_vocab=512)
        return total / count

    return jax.grad(loss, argnums=(0, 1)), (((rows, n_embd), BF16), ((vocab, n_embd), BF16), ((rows,), jnp.int32)), 2


def _fused_ce_rows(n_embd, walks, rows, vocab):
    """The per-row entry under a cotangent a row: the looped cell's four exits of 4,096 rows (16,384 rows of one call) at
    width 2048 against the whole 49,152-row head, and the 32,768 rows of a microbatch of 2."""
    def loss(hidden, head, labels, weights):
        return (fused_ce_rows(hidden, head, labels, block_rows=256, block_vocab=512) * weights).sum()

    exits = (walks, rows // SEQ, SEQ)
    return jax.grad(loss, argnums=(0, 1)), (((*exits, n_embd), BF16), ((vocab, n_embd), BF16), (exits, jnp.int32), (exits, F32)), 2


def _fused_rmsnorm(n_embd):
    def loss(x, scale):
        return fused_rms_norm(x, scale, None).astype(F32).sum()

    return jax.grad(loss, argnums=(0, 1)), (((4 * SEQ, n_embd), BF16), ((n_embd,), F32)), 2


def _quant_matmul(m):
    k, n = 2560, 7680
    return quant_matmul, (((m, k), BF16), ((k, n), jnp.int8), ((n,), F32)), 1


def _selective_scan(d_inner, batch=1, d_state=16):
    """The hybrid cell's recurrence (benchmark/configs/jamba2-3b-d14: 4096 x 5120 x 16), and
    the quarter of `d_inner` a shard holds under tp 4, which takes blocks of 256."""
    def loss(x, dt, a, b, c, h0):
        y, h = pallas_selective_scan(x, dt, a, b, c, h0, chunk=128)
        return y.sum() + h.sum()

    rows, narrow = ((batch, SEQ, d_inner), F32), ((batch, SEQ, d_state), F32)
    return jax.grad(loss, argnums=tuple(range(6))), (rows, rows, ((d_inner, d_state), F32), narrow, narrow, ((batch, d_inner, d_state), F32)), 2


def _gated_delta_state(backward, chunks=32, key_heads=16, r=2, chunk=64, dim=128):
    """The walk over a group's chunks at the gated-delta-rule cell's shapes (PR 45: 32 chunks of 64, 16 key heads with 2 value heads each,
    heads of 128 x 128, bfloat16), `plan_heads`' key heads a grid step: the forward alone, and a differentiated call, whose backward is the
    one kernel that sweeps the chunks forward, the state that came into each kept in VMEM (16 MiB here), and then backward."""
    heads = plan_heads(key_heads, r, chunks, chunk, dim, dim, BF16)
    assert heads * r == 8

    def forward(*operands):
        return walk(*operands, heads=heads)

    def loss(*operands):
        state, out = forward(*operands)
        return state.sum() + out.astype(F32).sum()

    per_chunk = lambda *last: ((chunks, 1, key_heads, r, chunk, *last), BF16)  # noqa: E731
    shapes = (((1, key_heads, r, dim, dim), F32), per_chunk(dim), per_chunk(dim), per_chunk(chunk), per_chunk(dim), per_chunk(dim), ((chunks, 1, key_heads, r), F32))
    if backward:
        return jax.grad(loss, argnums=tuple(range(7))), shapes, ("gated_delta_state_bwd",)  # the forward's outputs are not read: dead code
    return forward, shapes, ("gated_delta_state_fwd",)


def _head_norm(norm, heads, backward, tokens=4 * SEQ, dim=128):
    """The rule mixer's two norms over a head's channels at the gated-delta-rule cell's shapes (PR 46: one row of 16,384, q and k at 16
    heads of 128, o and z at 32, bfloat16, `out_norm_scale` float32), at the kernels' own block: the forward alone, and a differentiated
    call, which holds the forward too (its output is read by the loss) beside the backward kernel."""
    rows = ((1, tokens, heads, dim), BF16)
    forward, shapes = (head_l2_norm, (rows,)) if norm == "l2" else (lambda o, z, w: gated_head_rms_norm(o, z, w, eps=1e-6), (rows, rows, ((dim,), F32)))
    if backward:
        return jax.grad(lambda *xs: (forward(*xs).astype(F32) ** 2).sum(), argnums=tuple(range(len(shapes)))), shapes, HEAD_NORM_KERNELS[norm]
    return forward, shapes, HEAD_NORM_KERNELS[norm][:1]


def _moe_combine(width, held, k, tokens=4 * SEQ, block=256):
    """The expert layer's sum by token as both expert cells call it (PR 39): 16,384 tokens in blocks of 256, the forward's
    weighted sum and the backward's unweighted one over a table sized for every pair on held experts, with the kernel's
    padding rows; what a grid step holds in VMEM is counted under Mosaic's default scope, which the call leaves as it is."""
    from modalities_tpu.ops.expert_dispatch import TILE, rows_for

    assert vmem_bytes(block, width, held, 2) < 16 * 2**20

    def both(rows, pos, start, count, weight):
        return moe_combine(rows, pos, start, count, weight, block=block), moe_combine(rows, pos, start, count, block=block)

    by_token, by_block = (tokens, held), (tokens // block, held)
    rows = ((rows_for(k * tokens, held, TILE) + pad_rows(block), width), BF16)
    return both, (rows, (by_token, jnp.int32), (by_block, jnp.int32), (by_block, jnp.int32), (by_token, F32)), ("moe_combine", "moe_combine")


CASES = {
    "flash_fwd_bwd_d128": _flash(16, 16, 128),
    "flash_fwd_bwd_d80_gqa_32_8": _flash(32, 8, 80),
    "flash_fwd_bwd_d128_gqa_20_1": _flash(20, 1, 128),
    "flash_fwd_bwd_d128_gqa_12_4_s32768_two_kernels": _flash(12, 4, 128, seq=32768, kernels=TWO_KERNELS),
    "flash_ring_hop_not_causal_d128_gqa_4_1": _ring_hop_not_causal(),
    "flash_fwd_bwd_d192_dv128_b2_s8192_h32": _flash_two_widths(2, 8192, 32, 192, 128),
    # configs/config_kanana2_30b_a3b.yaml's own shape: sequence 4096, microbatch 4
    "flash_fwd_bwd_d192_dv128_b4_s4096_h32": _flash_two_widths(4, 4096, 32, 192, 128),
    # the window-and-global cell (PR 38): its window layers' call at three block pairs, its global layers' at 16,384, its head at width 2304
    "flash_window_fwd_bwd_w1024_s16384_gqa_32_4_b1024x1024": _flash_window(1024, 1024),
    "flash_window_fwd_bwd_w1024_s16384_gqa_32_4_b1024x512": _flash_window(1024, 512),
    "flash_window_fwd_bwd_w1024_s16384_gqa_32_4_b512x512": _flash_window(512, 512),
    "flash_fwd_bwd_d128_gqa_32_4_s16384": _flash(32, 4, 128, seq=4 * SEQ),
    # PR 42, the statistics as [B, H, 1, S] rows: the compressed-convolutional cell's 8 q on 2 kv heads at 8,192, and a row
    # shorter than a lane tile, one tile of 8 (`init_params`' dummy forward on a TPU; the backward for the layout's sake) or of 24
    "flash_fwd_bwd_d128_gqa_8_2_s8192": _flash(8, 2, 128, seq=2 * SEQ),
    "flash_fwd_bwd_d128_s8_one_short_tile": _flash(4, 4, 128, seq=8),
    "flash_fwd_bwd_d128_s24_one_tile_where_blocks_of_8_divide": _flash(4, 4, 128, seq=24),  # a [1, 8] block of [1, 24] does not lower
    # the gated-delta-rule cell's one attention layer (PR 44): 16 q on 2 kv heads of 256 at 16,384, and its head at 18,992 rows
    "flash_fwd_bwd_d256_gqa_16_2_s16384_at_the_tables_blocks": _flash_at_the_tables_blocks(16, 2, 256, 4 * SEQ),
    "fused_ce_fwd_bwd_e2048_rows16384_v18992": _fused_ce(2048, 4 * SEQ, vocab=18992),
    "fused_ce_fwd_bwd_e2304_rows16384_v12288": _fused_ce(2304, 4 * SEQ, vocab=12288),
    "fused_ce_fwd_bwd_e2048_rows16384_v16128": _fused_ce(2048, 4 * SEQ, vocab=16128),
    "fused_ce_rows_fwd_bwd_e2048_exits4_rows4096_v49152": _fused_ce_rows(2048, 4, SEQ, 49152),
    "fused_ce_rows_fwd_bwd_e2048_exits4_rows8192_v49152": _fused_ce_rows(2048, 4, 2 * SEQ, 49152),
    "fused_ce_fwd_bwd_e1536": _fused_ce(1536, SEQ),
    "fused_ce_fwd_bwd_e2560": _fused_ce(2560, 4 * SEQ),
    "fused_ce_fwd_bwd_e2560_rows8192": _fused_ce(2560, 2 * SEQ),
    "fused_ce_fwd_bwd_e2560_rows4096_v32768": _fused_ce(2560, SEQ, vocab=32768),
    "moe_combine_e2304_held8_k8_tokens16384": _moe_combine(2304, 8, 8),  # train-mellum2-12b-16k
    "moe_combine_e2048_held16_k6_tokens16384": _moe_combine(2048, 16, 6),  # train-kanana2-30b-8k
    "fused_rmsnorm_fwd_bwd_e1536": _fused_rmsnorm(1536),
    "fused_rmsnorm_fwd_bwd_e2560": _fused_rmsnorm(2560),
    "quant_matmul_m8": _quant_matmul(8),
    "quant_matmul_m256": _quant_matmul(256),
    "selective_scan_fwd_bwd_d5120": _selective_scan(5120),
    "selective_scan_fwd_bwd_b2_d1280": _selective_scan(1280, batch=2),
    "gated_delta_state_fwd_chunks32_heads32_d128": _gated_delta_state(backward=False),  # train-qwen3next-80b-16k
    "gated_delta_state_bwd_chunks32_heads32_d128": _gated_delta_state(backward=True),
    "head_l2_norm_fwd_rows16384x16_d128": _head_norm("l2", 16, backward=False),  # train-qwen3next-80b-16k: gdn/qk_norm
    "head_l2_norm_fwd_bwd_rows16384x16_d128": _head_norm("l2", 16, backward=True),
    "gated_head_rms_norm_fwd_rows16384x32_d128": _head_norm("gated", 32, backward=False),  # gdn/out_norm
    "gated_head_rms_norm_fwd_bwd_rows16384x32_d128": _head_norm("gated", 32, backward=True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e, case):
    fn, shapes, kernels = CASES[case]
    chip = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip) for shape, dtype in shapes]
    text = compiled_for_the_chip(jax.jit(fn).lower(*args)).as_text()  # raises what the chip's compiler would
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    if isinstance(kernels, tuple):  # which kernels, by the `name=` their calls carry
        assert sorted(re.search(r"(\w+)\)*/pallas_call", line).group(1) for line in calls) == sorted(kernels)
    else:
        assert len(calls) == kernels


# batch, sequence, n_embd, vocabulary of each cell's lookup (benchmark/configs/*/train.yaml)
LOOKUPS = {
    "train-2p7b-4k": (2, SEQ, 2560, VOCAB),
    "train-jamba2-3b-4k": (1, SEQ, 2560, 32768),
    "train-ouro-2p6b-4k": (1, SEQ, 2048, 49152),
    "train-kanana2-30b-8k": (2, 8192, 2048, 16128),
    "train-mellum2-12b-16k": (1, 4 * SEQ, 2304, 12288),
    "train-zaya1-8b-8k": (2, 8192, 2048, 32784),  # 16,384 ids against 16 x 2049 rows: over a quarter of them at a width the sorted form is fast at
}


@pytest.mark.parametrize("cell", sorted(LOOKUPS))
def test_embedding_gradient_follows_the_compilers_switch(v5e, cell):
    """The compiler sorts a scatter's indices when they number more than an eighth of the operand's rows, which in HBM
    costs 2.4 us a row (ops/embedding.py). Where the rule cuts the lookup into pieces, here the dense cell and the window-and-global cell, the compiled
    gradient holds the plan's scatters and no sort; elsewhere it is the program `jax.grad` of `jnp.take` compiles to. A
    libtpu that moves the switch fails here instead of silently costing the dense cell 17 ms a step."""
    batch, seq, n_embd, vocab = LOOKUPS[cell]
    chip = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in (((vocab, n_embd), BF16), ((batch, seq), jnp.int32), ((batch, seq, n_embd), BF16))]
    compiled = lambda lookup: compiled_for_the_chip(jax.jit(jax.grad(  # noqa: E731
        lambda table, ids, weights: (lookup(table, ids) * weights).astype(F32).sum())).lower(*args)).as_text()
    text, plan = compiled(embedding_lookup), grad_plan((batch, seq), vocab, n_embd, 2)
    if plan["form"] == "chunked":
        # the dense cell, and since PR 38 the window-and-global cell: 16,384 ids against the 12,288 rows this chip holds
        assert cell in ("train-2p7b-4k", "train-mellum2-12b-16k") and plan["rows_per_chunk"] <= vocab // 8
        assert len(re.findall(r" scatter\(", text)) == plan["chunks"] and " sort(" not in text
        assert " sort(" in compiled(lambda table, ids: jnp.take(table, ids, axis=0)), "the default the rule avoids"
    else:
        unnumbered = lambda text: re.sub(r"([A-Za-z_][\w\-]*)\.\d+", r"\1", without_metadata(text))  # noqa: E731  `%add.5` against `%add.3`
        assert unnumbered(text) == unnumbered(compiled(lambda table, ids: jnp.take(table, ids, axis=0)))
        assert (" sort(" in text) == (plan["rows"] > vocab // 8)


def test_two_layer_model_forward_backward_compiles_for_v5e(v5e, monkeypatch):
    """The 2.7B recipe's widths through `model.apply`, forward and backward, with
    the platform probes answering as a chip does: the fused norm and the flash
    kernel arrive through their dispatchers, and both backward passes lower."""
    from modalities_tpu.models.gpt2.gpt2_model import AttentionConfig, GPT2LLM
    from modalities_tpu.ops.pallas import autotune

    monkeypatch.setattr(jax, "devices", lambda *args, **kwargs: list(v5e[:1]))
    autotune.clear_cache()
    n_embd, heads = 2560, 32
    norm = {"norm_type": "rms_norm", "config": {"ndim": n_embd, "bias": False, "epsilon": 1e-5}}
    model = GPT2LLM(
        sample_key="input_ids", prediction_key="logits", poe_type="NOPE", sequence_length=SEQ,
        vocab_size=VOCAB, n_layer=2, n_head_q=heads, n_head_kv=8, n_embd=n_embd, ffn_hidden=11520,
        dropout=0.0, bias=False,
        attention_config=AttentionConfig(qkv_transforms=[{
            "type_hint": "RotaryTransform",
            "config": {"n_embd": n_embd, "n_head": heads, "base_freq": 10000},
        }]),
        attention_implementation="dao_flash", activation_type="swiglu",
        attention_norm_config=norm, ffn_norm_config=norm, lm_head_norm_config=norm,
        use_weight_tying=False, seed=0,
    )
    from flax.core import meta

    chip = SingleDeviceSharding(v5e[0])
    params = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=chip),
        meta.unbox(jax.eval_shape(model.init_params, jax.random.PRNGKey(0))),
    )
    tokens = jax.ShapeDtypeStruct((1, SEQ), jnp.int32, sharding=chip)

    def loss(params, tokens):
        return model.apply(params, {"input_ids": tokens})["logits"].astype(F32).mean()

    text = compiled_for_the_chip(jax.jit(jax.grad(loss)).lower(params, tokens)).as_text()
    kernel_lines = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in ("flash_attention_fwd", "flash_attention_bwd", "fused_rmsnorm_fwd", "fused_rmsnorm_bwd"):
        assert any(f"{kernel})" in line or f"{kernel}/" in line for line in kernel_lines), (kernel, len(kernel_lines))
    assert not any("flash_attention_bwd_d" in line for line in kernel_lines), "the two kernels the fused backward replaced"


def test_the_looped_steps_backward_adds_a_layers_gradient_into_one_stack(v5e, monkeypatch):
    """A looped stack (`loop_config`) at toy widths, 4 layers walked 4 times in bfloat16, gradient of a loss on every exit,
    compiled for the chip in both forms of its backward. Autodiff's transpose of the scan of scans (reached here as
    `selective_layer` at frequency 1: every block rematerialized, the hand-written rule not taken) adds each walk's
    stacked gradient `[L, ...]` into the accumulator inside the outer loop (`add_any`) and holds both; under `full` the
    rule of `gpt2_model._walks_in_place` adds a layer's slice where it stands: no `add_any` of a stack is left, and the
    step's temporaries fall by at least the stack (PR 37: 28.8 ms and 3.5 GiB of the cell `train-ouro-2p6b-4k`)."""
    from flax.core import meta

    from modalities_tpu.models.gpt2.gpt2_model import GPT2LLM, GPT2LLMConfig, GPT2Module
    from modalities_tpu.ops.pallas import autotune

    monkeypatch.setattr(jax, "devices", lambda *args, **kwargs: list(v5e[:1]))
    autotune.clear_cache()
    n_embd, ffn, layers, walks = 1024, 2816, 4, 4
    norm = {"norm_type": "rms_norm", "config": {"ndim": n_embd, "bias": False, "epsilon": 1e-6}}
    config = GPT2LLMConfig(
        sample_key="input_ids", prediction_key="logits", poe_type="NOPE", sequence_length=512, vocab_size=512, n_layer=layers,
        n_head_q=8, n_head_kv=8, n_embd=n_embd, ffn_hidden=ffn, dropout=0.0, bias=False,
        attention_config={"qkv_transforms": [{"type_hint": "RotaryTransform", "config": {"n_embd": n_embd, "n_head": 8, "base_freq": 1000000}}]},
        attention_implementation="dao_flash", activation_type="swiglu", attention_norm_config=norm, ffn_norm_config=norm,
        post_attention_norm_config=norm, post_ffn_norm_config=norm, lm_head_norm_config=norm, use_weight_tying=False,
        lm_head_chunk_size=64, loop_config={"total_ut_steps": walks, "beta": 0.1},
    )
    chip = SingleDeviceSharding(v5e[0])
    tokens = jax.ShapeDtypeStruct((2, 512), jnp.int32, sharding=chip)

    def compiled(variant):
        model = GPT2LLM(**config.model_dump()).with_spec_updates(remat_variant=variant, remat_freq=1, param_dtype="bfloat16")
        params = jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=chip),
                              meta.unbox(jax.eval_shape(model.init_params, jax.random.PRNGKey(0))))
        module = GPT2Module(model.config_spec, deterministic=False, output_exits=True)

        def loss(params, tokens):
            out = module.apply(params, tokens)
            return out["exits"].astype(F32).mean() + jnp.tanh(out["gate_logits"]).mean()

        stack = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(params["params"]["blocks"]))
        executable = compiled_for_the_chip(jax.jit(jax.grad(loss)).lower(params, tokens))
        sums = [line for line in executable.as_text().splitlines()
                if re.search(rf"= bf16\[{layers},\d+,\d+\]", line) and re.search(r'op_name="[^"]*/loop/while/body/[^"]*add_any"', line)]
        return executable.memory_analysis().temp_size_in_bytes, sums, stack

    by_walk, sums_by_walk, stack = compiled("selective_layer")
    in_place, sums_in_place, _ = compiled("full")
    assert stack > 2 * layers * 3 * n_embd * ffn
    assert sums_by_walk and not sums_in_place, (len(sums_by_walk), sums_in_place[:2])
    assert by_walk - in_place >= stack, (by_walk, in_place, stack)


def _compiled_cell_step(v5e, monkeypatch, tmp_path, config: str, vocab_size: int, sequence_length: int, chips: int = 1, layers=None):
    """The whole donated train step of `benchmark/configs/<config>/train.yaml` through the recipe's own components, compiled for
    the described v5e (`chips` of its four, `layers` deep where not the YAML's own depth): its text, and the compiler's peak
    (arguments + outputs + temporaries - aliases)."""
    import yaml

    from benchmark.traffic import packed_documents
    from modalities_tpu.ops.pallas import autotune
    from modalities_tpu.utils.recipe_validation import build_lowered_train_step

    monkeypatch.setattr(jax, "devices", lambda *args, **kwargs: list(v5e[:chips]))
    autotune.clear_cache()
    monkeypatch.chdir(tmp_path)  # the YAML's paths are relative; the corpus only has to exist and hold a step's rows
    mix = {"sequences": 8, "size_seed": 1, "doc_len_median": 600, "doc_len_sigma": 1.0, "doc_len_min": 32, "doc_len_max": 8192}
    packed_documents.generate(mix, 1, tmp_path / "data" / "train.pbin", vocab_size=vocab_size, sequence_length=sequence_length)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    path = os.path.join(repo, "benchmark", "configs", config, "train.yaml")
    if layers is not None:
        raw = yaml.safe_load(open(path).read())
        raw["model_raw"]["config"]["n_layer"] = layers
        path = tmp_path / "train.yaml"
        path.write_text(yaml.safe_dump(raw, sort_keys=False))
    executable = compiled_for_the_chip(build_lowered_train_step(path).lowered)
    memory = executable.memory_analysis()
    return executable.as_text(), memory.argument_size_in_bytes + memory.output_size_in_bytes + memory.temp_size_in_bytes - memory.alias_size_in_bytes


def test_the_compressed_convolutional_attention_cells_step_compiles_for_v5e(v5e, monkeypatch, tmp_path):
    """The whole donated train step of `benchmark/configs/zaya1-8b-ep2/train.yaml` (PR 40: 10 hybrid layers of width 2048, 8 of 16
    experts of 2048 held, 32,784 rows of the tied table, 2 rows of 8,192, every block rematerialized) through the recipe's own
    components, compiled for a described v5e: the mixer's shifts, both convolutions and the router's carried state lower beside
    the kernels the other cells share; no kernel of its own; the step fits the chip with the room `meta.json` states."""
    text, peak = _compiled_cell_step(v5e, monkeypatch, tmp_path, "zaya1-8b-ep2", 32784, 8192)
    kernels = sorted(set(re.findall(r"(\w+)\)*/pallas_call", text)))
    assert kernels == ["flash_attention_bwd", "flash_attention_fwd", "fused_ce_bwd_dw", "fused_ce_fwd", "fused_rmsnorm_bwd", "fused_rmsnorm_fwd"], kernels
    for scope in ("cca/conv", "cca/qk_norm", "cca/value_shift", "moe/router/router/eda", "moe/router/router/mlp", "residual/attn_merge"):
        assert f"/{scope}/" in text, scope
    assert 12.0 * 2**30 < peak < 14.7 * 2**30, peak / 2**30  # meta.json, memory_analysis: 12.76 GiB at 10 layers


def test_the_mesh_cells_step_reduce_scatters_its_row_parallel_products_over_tp(v5e, monkeypatch, tmp_path):
    """The four-chip cell's recipe (`benchmark/configs/modalities-2p7b-x4/train.yaml`: the 2.7B widths over `dp_shard 2 x tp 2`) at
    depth 2, compiled for the described `v5e:2x2`, read as the program's own record reads it (`collective_plan.plan_from_hlo_text`).
    What only the chip's compiler says of a sequence-parallel region's edges (PR 51: a sharding constraint alone splits the stream
    and leaves the partitioner's all-reduce and slice; a `psum_scatter` over the sequence dimension is taken apart into the same
    two; over a leading dimension it stays): inside the layer scans no all-reduce over tp, four reduce-scatters a layer (the row
    products' forward, the gathers' backward) and six all-gathers (the two gathers forward, again in the backward, and the row
    products' cotangents); the gradients still leave over dp_shard as reduce-scatters; no more memory than the partitioner's form."""
    from modalities_tpu.telemetry.collective_plan import plan_from_hlo_text

    layers, rows_seq_embd = 2, 1 * 4096 * 2560 * 2  # a dp_shard group's one row of 4,096 positions, bfloat16
    text, peak = _compiled_cell_step(v5e, monkeypatch, tmp_path, "modalities-2p7b-x4", 50304, 4096, chips=4, layers=layers)
    plan = plan_from_hlo_text(text, {"dp_shard": 2, "tp": 2})
    in_a_block = [row for row in plan["rows"] if "blocks/block/" in row["scope"] and row["bytes"] == rows_seq_embd]
    by_kind = {kind: sum(row["times"] for row in in_a_block if row["axis"] == "tp" and row["kind"] == kind)
               for kind in ("all-reduce", "reduce-scatter", "all-gather")}
    assert by_kind == {"all-reduce": 0, "reduce-scatter": 4 * layers, "all-gather": 6 * layers}, (by_kind, plan["totals"])
    assert {row["name"].split(".")[0] for row in in_a_block if row["kind"] == "reduce-scatter"} == {"reduce_scatter"}  # an instruction of its own
    assert plan["totals"]["dp_shard|reduce-scatter"]["count_a_run"] == 6 * layers + 1  # a weight's gradient; q and k share one, and the table's
    assert peak <= 2_225_738_752, peak  # the parent's peak at this depth (`scripts/collective_plan_for.py`, PR 51)
