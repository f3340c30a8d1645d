"""MFU calculator math (reference tests/utils/test_mfu.py — the analytic
flops-per-token value, peak-performance table, world-size scaling, and the
counted-parameters path through a real model)."""

import numpy as np
import pytest

# the reference's analytic architecture (test_mfu.py:32-41): GPT2-124M with
# absolute positions — N counts linear + embedding + layernorm params exactly
N_LAYER = 12
D_MODEL = 768
VOCAB_SIZE = 50304
SEQUENCE_LENGTH = 2048
N_ANALYTIC = (
    12 * N_LAYER * D_MODEL**2
    + (VOCAB_SIZE + SEQUENCE_LENGTH) * D_MODEL
    + (2 * N_LAYER + 1) * D_MODEL
)
ATTENTION_FLOPS = 12 * N_LAYER * D_MODEL * SEQUENCE_LENGTH
EXPECTED_FLOPS_PER_TOKEN = 6 * N_ANALYTIC + ATTENTION_FLOPS  # 977453568, reference :41


def test_mfu_calculator():
    from modalities_tpu.utils.mfu import GPT2MFUCalculator, get_peak_flops

    calc = GPT2MFUCalculator(
        n_layer=12, sequence_length=2048, n_embd=768, world_size=1, num_parameters=124_000_000
    )
    flops_per_token = 6 * 124_000_000 + 12 * 12 * 2048 * 768
    tokens_per_sec = 10_000
    expected = tokens_per_sec * flops_per_token / get_peak_flops()
    assert calc.compute(tokens_per_sec) == pytest.approx(expected)


def test_flops_per_token_matches_reference_analytic_value():
    """The reference pins 977,453,568 FLOPs/token for GPT2-124M (test_mfu.py:41);
    our 6N + 12*L*s*h with the SAME analytic N must reproduce it exactly."""
    assert EXPECTED_FLOPS_PER_TOKEN == 977_453_568
    from modalities_tpu.utils.mfu import GPT2MFUCalculator, get_peak_flops

    calc = GPT2MFUCalculator(
        n_layer=N_LAYER,
        sequence_length=SEQUENCE_LENGTH,
        n_embd=D_MODEL,
        world_size=1,
        num_parameters=N_ANALYTIC,
    )
    # compute(1 token/s) * peak == flops-per-token
    assert calc.compute(1.0) * get_peak_flops() == pytest.approx(EXPECTED_FLOPS_PER_TOKEN)


@pytest.mark.parametrize("world_size", [1, 2, 8, 64])
def test_world_size_scales_the_peak(world_size):
    """Reference semantics: tokens/s is the GLOBAL rate, so the denominator is
    world_size * per-chip peak — MFU at fixed throughput falls as 1/world."""
    from modalities_tpu.utils.mfu import GPT2MFUCalculator

    one = GPT2MFUCalculator(
        n_layer=2, sequence_length=64, n_embd=128, world_size=1, num_parameters=1000
    ).compute(5000.0)
    many = GPT2MFUCalculator(
        n_layer=2, sequence_length=64, n_embd=128, world_size=world_size, num_parameters=1000
    ).compute(5000.0)
    assert many == pytest.approx(one / world_size)


def test_counted_params_via_eval_shape_matches_real_init():
    """The wrapped_model path counts parameters abstractly (eval_shape — no buffer
    is materialized); the count must equal the real initialized tree's."""
    import jax

    from modalities_tpu.utils.mfu import GPT2MFUCalculator, _count_params
    from tests.models.test_gpt2_model import tiny_gpt2

    model = tiny_gpt2()
    counted = _count_params(model)
    params = model.init_params(jax.random.PRNGKey(0))
    exact = int(sum(np.prod(x.shape) for x in jax.tree.leaves(params)))
    assert counted == exact

    calc = GPT2MFUCalculator(
        n_layer=2, sequence_length=32, n_embd=128, world_size=1, wrapped_model=model
    )
    assert calc.num_parameters == exact


def test_analytic_n_matches_counted_params_for_gpt2_absolute():
    """Cross-check the reference's ANALYTIC N against a really-built model: a GPT2
    with absolute positions, 4d gelu FFN, weight tying and biased layernorms (the
    architecture the reference's N formula describes) must count to N_ANALYTIC
    up to the formula's known simplifications (it omits the qkv/proj biases)."""
    import jax

    from modalities_tpu.models.gpt2.gpt2_model import AttentionConfig
    from tests.models.test_gpt2_model import tiny_gpt2

    n_layer, n_embd, vocab, seq = 2, 128, 256, 64
    model = tiny_gpt2(
        "manual",
        attention_config=AttentionConfig(qkv_transforms=[]),
        poe_type="ABSOLUTE",
        n_layer=n_layer,
        n_embd=n_embd,
        vocab_size=vocab,
        sequence_length=seq,
        n_head_q=4,
        n_head_kv=4,
        ffn_hidden=4 * n_embd,
        activation_type="gelu",
        bias=False,
        use_weight_tying=True,
        attention_norm_config={"norm_type": "layer_norm", "config": {"normalized_shape": n_embd, "bias": False}},
        ffn_norm_config={"norm_type": "layer_norm", "config": {"normalized_shape": n_embd, "bias": False}},
        lm_head_norm_config={"norm_type": "layer_norm", "config": {"normalized_shape": n_embd, "bias": False}},
    )
    params = model.init_params(jax.random.PRNGKey(0))
    exact = int(sum(np.prod(x.shape) for x in jax.tree.leaves(params)))
    analytic = (
        12 * n_layer * n_embd**2  # qkv (3d^2) + proj (d^2) + gelu ffn (2*4d^2)
        + (vocab + seq) * n_embd  # wte + wpe
        + (2 * n_layer + 1) * n_embd  # pre-attn + pre-ffn + final norm scales
    )
    assert exact == analytic


# --------------------------------------------------------------- peak flops table


def test_peak_flops_known_kinds_no_warning(recwarn):
    from modalities_tpu.utils.mfu import TPU_PEAK_FLOPS, get_peak_flops

    assert get_peak_flops("TPU v5p") == 459e12
    assert get_peak_flops("TPU v5e") == 197e12
    assert get_peak_flops("TPU v4") == 275e12
    assert get_peak_flops("cpu") == 1e12
    assert get_peak_flops("TPU v6e") == TPU_PEAK_FLOPS["v6e"]
    assert len(recwarn) == 0


@pytest.mark.parametrize(
    "kind, expected",
    [
        # device_kind strings as the runtime reports them, not canonical names
        ("TPU v5 lite", 197e12),
        ("TPU v5p slice", 459e12),
        ("TPU v6e (Trillium)", 918e12),
        ("Cloud TPU v4-8", 275e12),
        ("CPU (virtual)", 1e12),
    ],
)
def test_peak_flops_kind_string_variants(kind, expected):
    """The table keys on substrings because device_kind strings vary by runtime
    (reference keys its GPU table on torch.cuda.get_device_name substrings)."""
    from modalities_tpu.utils.mfu import get_peak_flops

    assert get_peak_flops(kind) == expected


def test_peak_flops_unknown_kind_raises():
    """An unrecognized chip is an error: MFU is never scored against another chip's peak."""
    from modalities_tpu.utils.mfu import get_peak_flops

    with pytest.raises(ValueError, match="Unknown accelerator kind 'TPU v9'"):
        get_peak_flops("TPU v9")


def test_mfu_sane_range_for_realistic_numbers():
    """A check of the formula's arithmetic at a realistic size: the 680M model at
    64k context on a v5e at 4,043 tokens/s scores about 0.69 under it (the rate is
    an input of this test, not a measurement on record)."""
    from modalities_tpu.utils.mfu import GPT2MFUCalculator

    calc = GPT2MFUCalculator(
        n_layer=24,
        sequence_length=65536,
        n_embd=1536,
        world_size=1,
        num_parameters=680_000_000,
    )
    calc._peak = 197e12  # pin the v5e peak: the test must not depend on host kind
    mfu = calc.compute(4043.0)
    assert 0.60 < mfu < 0.75, mfu
