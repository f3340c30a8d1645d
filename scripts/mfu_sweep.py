"""Single-chip MFU tuning sweep: run ONE named experiment per process (a chip belongs
to one process at a time) and
print the same JSON line bench.py emits.

Usage:
    python scripts/mfu_sweep.py --list
    python scripts/mfu_sweep.py <experiment>   # e.g. mb16_full
    for e in $(python scripts/mfu_sweep.py --list); do \
        python scripts/mfu_sweep.py $e; done

Experiment axes: microbatch, flash block sizes (via MODALITIES_TPU_FLASH_BLOCK_Q/K),
remat policy (full vs selective-op save lists). BENCH_ITERS trims timing iterations.

Each line carries bench.py's full throughput split: `value`/`step_time_s` are
device-time (bench-comparable), `wall_step_time_s`/`tokens_per_sec_wall`/`mfu_wall`
time the whole dispatch+fetch loop, and `host_stall_s` is their difference.
`detail.goodput` breaks the whole candidate run into the telemetry subsystem's
goodput buckets (init / compile_first_step / train_step / other + goodput_pct) —
the same schema the Trainer publishes per interval, from the same ledger code.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (candidate tuple for bench._run_candidate, extra env)
# candidate: (name, n_layer, n_embd, n_head, ffn, seq, mb, attn_impl, param_dtype, remat)
_1B = (24, 2048, 16, 8192, 2048)


def _cand(name, mb, attn="dao_flash", remat="full", seq=2048):
    n_layer, n_embd, n_head, ffn, _ = _1B
    return (name, n_layer, n_embd, n_head, ffn, seq, mb, attn, "bfloat16", remat)


# Block sizes are pinned explicitly in every entry (the ops/attention.py default
# moved 128 -> 1024 from this sweep's results; unpinned entries would silently stop
# reproducing the configuration their names record).
_B128 = {"MODALITIES_TPU_FLASH_BLOCK_Q": "128", "MODALITIES_TPU_FLASH_BLOCK_K": "128"}

EXPERIMENTS: dict[str, tuple[tuple, dict[str, str]]] = {
    "mb8_full_128": (_cand("mb8_full_128", 8), dict(_B128)),
    "mb16_full_128": (_cand("mb16_full_128", 16), dict(_B128)),
    "mb8_full_256": (_cand("mb8_full_256", 8), {"MODALITIES_TPU_FLASH_BLOCK_Q": "256", "MODALITIES_TPU_FLASH_BLOCK_K": "256"}),
    "mb8_full_512": (_cand("mb8_full_512", 8), {"MODALITIES_TPU_FLASH_BLOCK_Q": "512", "MODALITIES_TPU_FLASH_BLOCK_K": "512"}),
    "mb8_full_q256_k1024": (_cand("mb8_full_q256_k1024", 8), {"MODALITIES_TPU_FLASH_BLOCK_Q": "256", "MODALITIES_TPU_FLASH_BLOCK_K": "1024"}),
    "mb8_full_q512_k1024": (_cand("mb8_full_q512_k1024", 8), {"MODALITIES_TPU_FLASH_BLOCK_Q": "512", "MODALITIES_TPU_FLASH_BLOCK_K": "1024"}),
    "mb8_full_1024": (_cand("mb8_full_1024", 8), {"MODALITIES_TPU_FLASH_BLOCK_Q": "1024", "MODALITIES_TPU_FLASH_BLOCK_K": "1024"}),
    "mb8_save_attn_512": (_cand("mb8_save_attn_512", 8, remat="selective_op:attn_out"), {"MODALITIES_TPU_FLASH_BLOCK_Q": "512", "MODALITIES_TPU_FLASH_BLOCK_K": "512"}),
    "mb4_save_attn_512": (_cand("mb4_save_attn_512", 4, remat="selective_op:attn_out"), {"MODALITIES_TPU_FLASH_BLOCK_Q": "512", "MODALITIES_TPU_FLASH_BLOCK_K": "512"}),
    "mb8_save_attn": (_cand("mb8_save_attn", 8, remat="selective_op:attn_out"), dict(_B128)),
    "mb16_save_attn": (_cand("mb16_save_attn", 16, remat="selective_op:attn_out"), dict(_B128)),
    "mb8_save_dots": (_cand("mb8_save_dots", 8, remat="selective_op:matmul"), dict(_B128)),
    "mb8_sdpa_full": (_cand("mb8_sdpa_full", 8, attn="pytorch_flash"), {}),
    "mb4_sdpa_full": (_cand("mb4_sdpa_full", 4, attn="pytorch_flash"), {}),
    "mb2_noremat_1024": (_cand("mb2_noremat_1024", 2, remat=None), {"MODALITIES_TPU_FLASH_BLOCK_Q": "1024", "MODALITIES_TPU_FLASH_BLOCK_K": "1024"}),
    "mb4_noremat_1024": (_cand("mb4_noremat_1024", 4, remat=None), {"MODALITIES_TPU_FLASH_BLOCK_Q": "1024", "MODALITIES_TPU_FLASH_BLOCK_K": "1024"}),
    "mb8_full_q1024_k2048": (_cand("mb8_full_q1024_k2048", 8), {"MODALITIES_TPU_FLASH_BLOCK_Q": "1024", "MODALITIES_TPU_FLASH_BLOCK_K": "2048"}),
    "mb16_full_1024": (_cand("mb16_full_1024", 16), {"MODALITIES_TPU_FLASH_BLOCK_Q": "1024", "MODALITIES_TPU_FLASH_BLOCK_K": "1024"}),
}

# --- round-2 late sweep: context-length ladder + chunked-head variants ----------
# 680M dims (the 32k headline model) at longer contexts, and the 1.3B at 4k/8k with
# and without the fused chunked lm-head+loss (which at mb8/seq2048 otherwise
# materializes [8,2048,50304] fp32 logits = 3.3 GB).
_680M = (24, 1536, 12, 6144)


def _cand680(name, seq, chunk, mb=1):
    n_layer, n_embd, n_head, ffn = _680M
    return (name, n_layer, n_embd, n_head, ffn, seq, mb, "dao_flash", "bfloat16", "full", chunk)


def _cand1b_chunk(name, seq, mb, chunk):
    n_layer, n_embd, n_head, ffn, _ = _1B
    return (name, n_layer, n_embd, n_head, ffn, seq, mb, "dao_flash", "bfloat16", "full", chunk)


# every entry pins its flash block sizes (the file rule above): the ladder ran at
# the 1024 default, so 1024 is what these names record
_B1024 = {"MODALITIES_TPU_FLASH_BLOCK_Q": "1024", "MODALITIES_TPU_FLASH_BLOCK_K": "1024"}

EXPERIMENTS.update(
    {
        "680m_48k_chunk2048": (_cand680("680m_48k_chunk2048", 49152, 2048), dict(_B1024)),
        "680m_96k_chunk2048": (_cand680("680m_96k_chunk2048", 98304, 2048), dict(_B1024)),
        "680m_64k_chunk2048": (_cand680("680m_64k_chunk2048", 65536, 2048), dict(_B1024)),
        "680m_64k_q512_k2048": (
            _cand680("680m_64k_q512_k2048", 65536, 2048),
            {"MODALITIES_TPU_FLASH_BLOCK_Q": "512", "MODALITIES_TPU_FLASH_BLOCK_K": "2048"},
        ),
        "680m_64k_q2048_k512": (
            _cand680("680m_64k_q2048_k512", 65536, 2048),
            {"MODALITIES_TPU_FLASH_BLOCK_Q": "2048", "MODALITIES_TPU_FLASH_BLOCK_K": "512"},
        ),
        "680m_32k_chunk4096": (_cand680("680m_32k_chunk4096", 32768, 4096), dict(_B1024)),
        "680m_32k_chunk1024": (_cand680("680m_32k_chunk1024", 32768, 1024), dict(_B1024)),
        "680m_32k_mb2_chunk2048": (_cand680("680m_32k_mb2_chunk2048", 32768, 2048, mb=2), dict(_B1024)),
        "1.3b_4096_mb4": (_cand("1.3b_4096_mb4", 4, seq=4096), dict(_B1024)),
        "1.3b_4096_mb4_chunk1024": (_cand1b_chunk("1.3b_4096_mb4_chunk1024", 4096, 4, 1024), dict(_B1024)),
        "1.3b_8192_mb2_chunk2048": (_cand1b_chunk("1.3b_8192_mb2_chunk2048", 8192, 2, 2048), dict(_B1024)),
        "1.3b_16k_mb1_chunk2048": (_cand1b_chunk("1.3b_16k_mb1_chunk2048", 16384, 1, 2048), dict(_B1024)),
        "1.3b_32k_mb1_chunk2048": (_cand1b_chunk("1.3b_32k_mb1_chunk2048", 32768, 1, 2048), dict(_B1024)),
        "1.3b_2048_mb8_chunk512": (_cand1b_chunk("1.3b_2048_mb8_chunk512", 2048, 8, 512), dict(_B1024)),
        "1.3b_2048_mb8_chunk1024": (_cand1b_chunk("1.3b_2048_mb8_chunk1024", 2048, 8, 1024), dict(_B1024)),
    }
)


def main() -> None:
    if len(sys.argv) != 2 or sys.argv[1] in ("-h", "--help"):
        print(__doc__)
        raise SystemExit(2)
    if sys.argv[1] == "--list":
        print("\n".join(EXPERIMENTS))
        return
    name = sys.argv[1]
    cand, env = EXPERIMENTS[name]
    os.environ.update(env)

    import bench

    iters = int(os.environ.get("BENCH_ITERS", "10"))
    try:
        result = bench._run_candidate(cand, iters)
    except Exception as exc:  # OOM / lowering failures are sweep data, not crashes
        result = {"experiment": name, "error": f"{type(exc).__name__}: {str(exc)[:300]}"}
    result["experiment"] = name
    print(json.dumps(result))


if __name__ == "__main__":
    main()
