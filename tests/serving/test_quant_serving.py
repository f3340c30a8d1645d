"""Quantized serving acceptance (ISSUE 14): the int8/fp8 weight-only engine and
the int8 paged KV pool preserve EVERY serving invariant — one decode and one
prefill executable, clean pool audits, deterministic preemption replay, the
swap quantization-drift gate — while the logit-error oracle (quant/oracle.py)
replaces the bitwise parity pins quantized modes are excluded from.
"""

import jax
import jax.numpy as jnp
import pytest
from flax.core import meta

from modalities_tpu.quant.weights import quantize_params
from modalities_tpu.resilience.events import counts_since, snapshot_counts
from modalities_tpu.serving.engine import ServingEngine
from modalities_tpu.telemetry.metrics import MetricsRegistry, parse_prometheus_text
from tests.models.test_gpt2_model import tiny_gpt2

REQS = [
    ([3, 17, 42, 9, 77], 8, 0.0, 0),
    ([7, 7, 7], 5, 0.8, 1),
    (list(range(1, 18)), 6, 0.0, 2),  # prompt spans 3 blocks
    ([99, 3, 55, 8, 120], 6, 0.8, 3),
]


@pytest.fixture(scope="module")
def model():
    return tiny_gpt2("manual")


@pytest.fixture(scope="module")
def params(model):
    return meta.unbox(model.init_params(jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def quant_engine(model, params):
    """The fully-quantized engine: int8 weights AND int8 KV blocks, paged."""
    return ServingEngine(
        model, params, max_batch_slots=2, kv_cache="paged", paged_block_size=8,
        quant_weights="int8", quant_kv="int8", metrics=MetricsRegistry(),
    )


def _run(engine, reqs=REQS):
    rids = [engine.submit(p, b, temperature=t, seed=s) for p, b, t, s in reqs]
    results = engine.run()
    return [results[rid] for rid in rids]


# -------------------------------------------------------- engine invariants


def test_quant_engine_preserves_every_serving_invariant(quant_engine):
    """Mixed greedy/sampled batch through the int8/int8 engine: legal budget
    finishes, ONE decode and ONE prefill executable, the pool audit clean and
    every block (and scale slot) returned."""
    results = _run(quant_engine)
    for result in results:
        assert result.finish_reason == "budget"
        assert len(result.tokens) > 0
    stats = quant_engine.stats()
    assert stats["decode_executables"] == 1
    assert stats["prefill_executables"] == 1
    assert stats["free_blocks"] == stats["num_blocks"]
    assert stats["quant_weights"] == "int8"
    assert stats["quant_kv"] == "int8"
    assert stats["quant_bytes_saved"] > 0
    assert stats["kv_pool_bytes"] > 0
    quant_engine._table_state.check()


def test_quant_cache_tree_carries_int8_pools_and_f32_scales(quant_engine):
    dtypes = {jnp.dtype(leaf.dtype) for leaf in jax.tree.leaves(quant_engine.cache)}
    assert jnp.dtype(jnp.int8) in dtypes  # the data pools
    assert jnp.dtype(jnp.float32) in dtypes  # the per-(block,row,head) scales
    # params really are stored quantized (int8 kernels + scale siblings)
    kernel_dtypes = {
        jnp.dtype(leaf.dtype) for leaf in jax.tree.leaves(quant_engine.params)
    }
    assert jnp.dtype(jnp.int8) in kernel_dtypes


def test_quant_metrics_exported(quant_engine):
    parsed = parse_prometheus_text(quant_engine.metrics.render())
    assert parsed["serve_kv_pool_bytes"][()] > 0
    assert parsed["serve_quant_weights_bytes_saved"][()] > 0
    info = parsed["serve_quant_mode_info"]
    (labels,) = info.keys()
    assert dict(labels) == {"weights": "int8", "kv": "int8"}
    assert info[labels] == 1.0


def test_quant_kv_requires_paged_cache(model, params):
    with pytest.raises(ValueError, match="requires kv_cache='paged'"):
        ServingEngine(model, params, max_batch_slots=1, quant_kv="int8")


def test_pre_quantized_mode_mismatch_rejected(model, params):
    fp8_params = quantize_params(params, "fp8")
    with pytest.raises(ValueError, match="load_serving_params"):
        ServingEngine(
            model, fp8_params, max_batch_slots=1, quant_weights="int8",
            metrics=MetricsRegistry(),
        )


def test_engine_quantizes_identically_to_the_load_seam(model, params, quant_engine):
    """The single-seam contract: an engine handed RAW params (quantizing them
    itself) and an engine handed params pre-quantized through the
    load_serving_params path serve token-identical generations."""
    pre = ServingEngine(
        model, quantize_params(params, "int8"), max_batch_slots=2,
        kv_cache="paged", paged_block_size=8,
        quant_weights="int8", quant_kv="int8", metrics=MetricsRegistry(),
    )
    for a, b in zip(_run(quant_engine), _run(pre)):
        assert a.tokens == b.tokens


# ------------------------------------------------ preemption replay (quantized)


def test_preemption_replay_deterministic_on_quantized_pool(model, params):
    """The seed-replay determinism contract survives quantization: a pool too
    small for both requests preempts the youngest, and re-admission reproduces
    the EXACT tokens an ample-pool quantized engine produces — quantize-on-write
    is a pure function of the (replayed) token stream."""

    def quant_paged(num_blocks):
        return ServingEngine(
            model, params, max_batch_slots=2, kv_cache="paged",
            paged_block_size=4, paged_max_len=24, paged_num_blocks=num_blocks,
            quant_weights="int8", quant_kv="int8", metrics=MetricsRegistry(),
        )

    reqs = [(list(range(1, 9)), 15, 0.0, 0), ([5, 9, 2], 20, 0.8, 1)]
    ample = _run(quant_paged(16), reqs)
    tight_engine = quant_paged(9)  # one block short of peak demand
    tight = _run(tight_engine, reqs)
    stats = tight_engine.stats()
    assert stats["preemptions"] >= 1
    for a, b in zip(ample, tight):
        assert a.tokens == b.tokens
        assert b.finish_reason == "budget"
    assert stats["free_blocks"] == stats["num_blocks"]
    tight_engine._table_state.check()


# -------------------------------------------------------- oracle gate (CPU)


def test_logit_oracle_gates_the_fully_quantized_mode(model, params):
    """The acceptance gate that replaces the bitwise pins: greedy token match
    >= 99% with a bounded max-abs logit error. Tier-1 runs the tightest combo
    (int8 weights + int8 KV — both error sources stacked); the per-mode sweep
    is the slow test below."""
    from modalities_tpu.quant.oracle import run_oracle

    report = run_oracle(
        model, params, [[1, 2, 3, 4, 5]],
        quant_weights="int8", quant_kv="int8", max_new_tokens=4,
    )
    assert report.token_match >= 0.99, report.token_match
    assert report.max_abs_err <= 0.2, report.max_abs_err
    assert report.positions == 4


@pytest.mark.slow  # ~60 s; the stacked int8/int8 combo above stays tier-1
def test_logit_oracle_gates_every_quantized_mode(model, params):
    from modalities_tpu.quant.oracle import run_oracle

    prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [3, 1, 4, 1, 5, 9, 2, 6]]
    for qw, qkv, bound in [("int8", "none", 0.1), ("none", "int8", 0.1), ("fp8", "int8", 0.2)]:
        report = run_oracle(
            model, params, prompts, quant_weights=qw, quant_kv=qkv, max_new_tokens=6
        )
        assert report.token_match >= 0.99, (qw, qkv, report.token_match)
        assert report.max_abs_err <= bound, (qw, qkv, report.max_abs_err)
        assert report.positions == 18


# ----------------------------------------------------- perfscope on quantized


def test_perfscope_buckets_quantized_decode_and_sums_to_total(quant_engine):
    """The static-closure pin extends to the quantized decode executable: the
    dequant ops (int8 convert + scale multiplies) land in buckets and the
    per-bucket costs still sum EXACTLY to the module total."""
    report = quant_engine.perfscope_report()
    total = report["total"]
    for key in ("ops", "flops", "bytes"):
        assert sum(b[key] for b in report["buckets"].values()) == total[key], key
    assert total["flops"] > 0
    assert "matmul" in report["buckets"]


# ----------------------------------------------------------- swap drift gate


def test_swap_rejects_quant_mode_drift_with_rollback_event(quant_engine, params):
    """A fleet rollout can NEVER install a generation whose quantization mode
    differs from the incumbent's: bf16 and fp8 offers are rejected before any
    leaf comparison, with a fleet/rollback stage=quant event recorded."""
    before = snapshot_counts()
    with pytest.raises(ValueError, match="quantization mode drift"):
        quant_engine.swap_weights(params)  # unquantized offer
    with pytest.raises(ValueError, match="quantization mode drift"):
        quant_engine.swap_weights(quantize_params(params, "fp8"))
    assert counts_since(before).get("fleet", 0) == 2
    # a same-mode generation still swaps cleanly on the same executable: the new generation decodes on the one the engine had
    # (or, where this test is the module's first on its worker, on the first it compiles)
    gen_before, executables_before = quant_engine.weights_generation, quant_engine.stats()["decode_executables"]
    quant_engine.swap_weights(quantize_params(params, "int8"))
    assert quant_engine.weights_generation == gen_before + 1
    _run(quant_engine)
    assert quant_engine.stats()["decode_executables"] == max(executables_before, 1)
