"""In-app vs bench MFU on ONE config — the Δ<2% check for a hardware window
(VERDICT r4 #8: both columns on the ladder rows).

Runs, in this order and in THIS process's single chip claim:
1. bench-style timing of the matching candidate (dispatch-ahead, fetch-behind,
   median-of-best-repeat — bench._run_candidate), then
2. a REAL `Main.run` of the config for a few intervals over a synthetic corpus,
   taking the PEAK interval MFU from the evaluation_results stream (peak skips the
   compile-polluted first interval).

Prints one JSON line: {"config", "bench_mfu", "in_app_mfu", "delta_pct",
"within_2pct"}. With the round-5 deferred-publish overlap in the trainer the two
loops have the same dispatch/fetch structure, so the delta should be noise.

Usage (TPU):  python scripts/inapp_vs_bench.py [--steps 12] [--log_interval 3]
CPU smoke:    JAX_PLATFORMS=cpu python scripts/inapp_vs_bench.py --cpu_smoke
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = Path(__file__).resolve().parent.parent


def _in_app_peak_mfu(config_path: Path, steps: int, log_interval: int, seq: int, vocab: int,
                     mbs: int, dp: int) -> float:
    """Drive Main.run on a shrunk-step twin of the config and return the peak
    interval MFU the trainer published."""
    import numpy as np
    import yaml

    from modalities_tpu.dataloader.packed_data import write_pbin_file
    from modalities_tpu.main import Main

    cfg = yaml.safe_load(config_path.read_text())
    tt = cfg["settings"]["training_target"]
    tt["num_target_steps"] = steps
    tt["num_target_tokens"] = steps * mbs * seq * dp
    iv = cfg["settings"]["intervals"]
    iv["training_log_interval_in_steps"] = log_interval
    iv["checkpointing_interval_in_steps"] = steps
    iv["evaluation_interval_in_steps"] = steps

    with tempfile.TemporaryDirectory() as tmp:
        tmp = Path(tmp)
        (tmp / "data").mkdir()
        rng = np.random.default_rng(0)
        corpus = tmp / "data" / Path(cfg["settings"]["paths"]["train_dataset_path"]).name
        need = (steps + 2) * mbs * dp * (seq + 1) + seq
        write_pbin_file(corpus, iter([rng.integers(0, vocab, size=need)]), token_size_in_bytes=2)
        cfg["settings"]["paths"]["train_dataset_path"] = str(corpus)
        twin = tmp / "inapp_twin.yaml"
        twin.write_text(yaml.safe_dump(cfg, default_flow_style=False, sort_keys=False))

        cwd = os.getcwd()
        os.chdir(tmp)
        try:
            main = Main(twin, experiments_root_path=tmp / "data" / "experiments",
                        experiment_id="inapp_vs_bench")
            main.run(main.build_components())
        finally:
            os.chdir(cwd)
        results = tmp / "data" / "experiments" / "inapp_vs_bench" / "evaluation_results.jsonl"
        mfus = []
        for line in results.read_text().splitlines():
            rec = json.loads(line)
            if rec.get("dataloader_tag") == "train" and "MFU" in rec.get("throughput_metrics", {}):
                mfus.append(float(rec["throughput_metrics"]["MFU"]))
        if not mfus:
            raise RuntimeError(f"no train MFU lines in {results}")
        return max(mfus)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--config", type=Path, default=REPO / "configs" / "config_long_context_32k.yaml")
    p.add_argument("--candidate", default="680m_32k_flash_chunked",
                   help="bench._TPU_CANDIDATES entry matching the config's model")
    p.add_argument("--steps", type=int, default=12)
    p.add_argument("--log_interval", type=int, default=3)
    p.add_argument("--cpu_smoke", action="store_true",
                   help="tiny dims on CPU: exercises the full flow, numbers meaningless")
    args = p.parse_args()

    import bench

    if args.cpu_smoke:
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
        cand = bench._CPU_CANDIDATE
        config = REPO / "configs" / "config_lorem_ipsum_tpu.yaml"
        seq, vocab, mbs, dp = 64, 256, 8, 8
    else:
        cand = next(c for c in bench._TPU_CANDIDATES if c[0] == args.candidate)
        config = args.config
        seq, vocab, mbs, dp = cand[5], 50304, cand[6], 1

    # 1. bench column first (the leader-first discipline: the dispatch-ahead number
    #    is the anchor; a degraded window shows up in its repeats_s evidence)
    bench_result = bench._run_candidate(cand, int(os.environ.get("BENCH_ITERS", "4")))
    bench_mfu = bench_result["value"]

    # 2. in-app column through the REAL config + Trainer
    in_app = _in_app_peak_mfu(config, args.steps, args.log_interval, seq, vocab, mbs, dp)

    delta_pct = abs(bench_mfu - in_app) / max(bench_mfu, 1e-9) * 100
    print(json.dumps({
        "config": str(config.name),
        "candidate": cand[0],
        "bench_mfu": round(bench_mfu, 4),
        "in_app_mfu": round(in_app, 4),
        "delta_pct": round(delta_pct, 2),
        "within_2pct": bool(delta_pct < 2.0),
        "bench_detail": {k: bench_result["detail"].get(k) for k in
                         ("tokens_per_sec", "step_time_s", "repeats_s", "device")},
    }))


if __name__ == "__main__":
    main()
