"""Debug stats, nan detection, repeating loader, tokenization verification."""

import json

import jax.numpy as jnp
import numpy as np
import pytest


def test_collect_tree_stats_flags_nonfinite(tmp_path):
    from modalities_tpu.utils.debug_components import DebugStatsLogger, collect_tree_stats, has_nonfinite

    tree = {"good": jnp.ones((4, 4)), "bad": jnp.asarray([1.0, jnp.nan, jnp.inf])}
    stats = collect_tree_stats(tree)
    assert stats["good"]["nan_count"] == 0
    assert stats["bad"]["nan_count"] == 1
    assert stats["bad"]["inf_count"] == 1
    assert has_nonfinite(tree)
    assert not has_nonfinite({"x": jnp.ones(3)})

    dbg_logger = DebugStatsLogger(tmp_path, log_interval_steps=1)
    dbg_logger.log(0, params=tree)
    dbg_logger.close()
    rec = json.loads((tmp_path / "debug_stats_rank_0.jsonl").read_text().splitlines()[0])
    assert rec["params"]["params/bad"]["nan_count"] == 1


def test_repeating_dataloader_bumps_epoch(tmp_path):
    from modalities_tpu.dataloader.dataloader import LLMDataLoader
    from modalities_tpu.dataloader.repeating_dataloader import RepeatingDataLoader
    from modalities_tpu.dataloader.samplers import BatchSampler, ResumableDistributedSampler

    dataset = [{"x": np.asarray([i])} for i in range(8)]
    sampler = ResumableDistributedSampler(dataset, rank=0, num_replicas=1, shuffle=True, seed=1)
    loader = LLMDataLoader("train", dataset, BatchSampler(sampler, 2, True), collate_fn=None,
                           num_prefetch_batches=0)
    repeating = RepeatingDataLoader(loader, reshuffle_after_epoch=True)
    it = iter(repeating)
    first_epoch = [next(it) for _ in range(4)]
    second_epoch = [next(it) for _ in range(4)]
    assert repeating.current_epoch == 1
    assert sampler.epoch == 1
    flat1 = [int(d["x"][0]) for b in first_epoch for d in b]
    flat2 = [int(d["x"][0]) for b in second_epoch for d in b]
    assert sorted(flat1) == sorted(flat2) == list(range(8))
    assert flat1 != flat2  # reshuffled


def test_verify_tokenization_consistency(tmp_path):
    from modalities_tpu.utils.verify_tokenization_consistency import verify_tokenization_consistency

    src = tmp_path / "d.jsonl"
    src.write_text('\n'.join('{"text": "doc %d words"}' % i for i in range(5)) + "\n")

    class Tok:
        vocab_size = 300

        def tokenize(self, text):
            return [ord(c) % 250 for c in text]

        def get_token_id(self, t):
            return 255

    verify_tokenization_consistency(src, eod_token="<eod>", tokenizer=Tok())


def test_verify_tokenization_detects_mismatch(tmp_path):
    from modalities_tpu.utils.verify_tokenization_consistency import verify_tokenization_consistency

    src = tmp_path / "d.jsonl"
    src.write_text('{"text": "abc"}\n')

    marker = tmp_path / "first_call_done"

    class FlakyTok:
        # nondeterministic across calls; file-based state survives the pack worker fork
        vocab_size = 300

        def tokenize(self, text):
            if marker.exists():
                return [9, 9, 9]
            marker.touch()
            return [1, 2, 3]

        def get_token_id(self, t):
            return 255

    with pytest.raises(ValueError, match="mismatch"):
        verify_tokenization_consistency(src, eod_token="<eod>", tokenizer=FlakyTok())


def test_analyze_debug_log_roundtrip(tmp_path):
    """The analysis CLI consumes what DebugStatsLogger writes (reference ships this
    loop as the model_step_analyser notebook): filter by step/tree, sort by any
    stats column, isolate non-finite tensors."""
    import jax.numpy as jnp
    import numpy as np

    from modalities_tpu.utils.debug_components import (
        DebugStatsLogger,
        analyze_debug_log,
        format_debug_log_rows,
    )

    dbg = DebugStatsLogger(tmp_path, log_interval_steps=1)
    good = {"w": jnp.ones((4, 4)), "b": jnp.full((2,), 3.0)}
    bad = {"w": jnp.asarray([np.nan, 1.0]), "b": jnp.asarray([np.inf, 2.0, 4.0])}
    dbg.log(0, params=good)
    dbg.log(1, params=good, grads=bad)
    dbg.close()

    path = tmp_path / "debug_stats_rank_0.jsonl"
    rows = analyze_debug_log(path, sort_by="max", top=None)
    assert {(r["step"], r["tree"]) for r in rows} == {(0, "params"), (1, "params"), (1, "grads")}
    assert rows[0]["max"] >= rows[-1]["max"]  # descending by default

    only_bad = analyze_debug_log(path, nonfinite_only=True, top=None)
    assert {(r["tree"], r["tensor"]) for r in only_bad} == {
        ("grads", "grads/w"), ("grads", "grads/b"),
    }
    assert any(r["nan_count"] == 1 for r in only_bad)
    assert any(r["inf_count"] == 1 for r in only_bad)

    step1 = analyze_debug_log(path, step=1, tree="params", sort_by="mean", ascending=True, top=1)
    assert len(step1) == 1 and step1[0]["step"] == 1 and step1[0]["tree"] == "params"

    with pytest.raises(ValueError, match="sort_by"):
        analyze_debug_log(path, sort_by="not_a_column")

    table = format_debug_log_rows(rows)
    assert "tensor" in table.splitlines()[0] and "params/w" in table


def test_analyze_debug_logs_cli(tmp_path):
    """The real `data analyze_debug_logs` entry point over a written stream."""
    import subprocess
    import sys

    import jax.numpy as jnp

    from modalities_tpu.utils.debug_components import DebugStatsLogger

    dbg = DebugStatsLogger(tmp_path, log_interval_steps=1)
    dbg.log(0, params={"w": jnp.ones((2, 2))})
    dbg.close()
    out = subprocess.run(
        [sys.executable, "-m", "modalities_tpu", "data", "analyze_debug_logs",
         "--log_file_path", str(tmp_path / "debug_stats_rank_0.jsonl"), "--as_json"],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    import json as _json

    rows = [_json.loads(line) for line in out.stdout.splitlines() if line.strip().startswith("{")]
    assert rows and rows[0]["tensor"] == "params/w" and rows[0]["max"] == 1.0


# ------------------------------------------------------------------ hashed seeds


@pytest.mark.parametrize(
    "input_data, max_seed",
    [
        (["a", "b", "c"], 2**32 - 1),
        (["d", "e", "f"], 2**32 - 1),
        (["g", "hij", "klmnop"], 2**32 - 1),
        (["5d3b0e03a13dff183d4d77bc258bec18"] * 3, 2**32 - 1),
        (["123", "456", "789"], 97),
    ],
)
def test_calculate_hashed_seed_in_range(input_data, max_seed):
    """Reference tests/utils/test_seeding.py grid: always in [0, max_seed)."""
    from modalities_tpu.utils.seeding import calculate_hashed_seed

    seed = calculate_hashed_seed(input_data=input_data, max_seed=max_seed)
    assert 0 <= seed < max_seed


def test_calculate_hashed_seed_matches_reference_construction():
    """Pin the exact digest-sum construction (sha256 per string, summed, mod) so the
    derived chunk seeds stay byte-compatible with the reference's."""
    import hashlib

    from modalities_tpu.utils.seeding import calculate_hashed_seed

    data = ["42", "7"]
    expected = sum(int(hashlib.sha256(x.encode()).hexdigest(), 16) for x in data) % (2**32 - 1)
    assert calculate_hashed_seed(data) == expected


def test_hashed_seed_decorrelates_neighboring_pairs():
    """The reason hashing replaced global_seed + chunk_id in api.py: (5, 1) and
    (4, 2) must derive DIFFERENT seeds (arithmetic addition collides them)."""
    from modalities_tpu.utils.seeding import calculate_hashed_seed

    a = calculate_hashed_seed(["5", "1"])
    b = calculate_hashed_seed(["4", "2"])
    assert a != b
    assert calculate_hashed_seed(["5", "1"]) == a  # deterministic


def test_shuffled_chunks_differ_across_chunk_ids(tmp_path):
    """Two chunks of the same corpus under one global_seed must not share a
    permutation pattern (the api-level consequence of hashed seeds)."""
    import numpy as np

    from modalities_tpu.api import create_shuffled_jsonl_dataset_chunk

    src = tmp_path / "d.jsonl"
    lines = ['{"text": "doc %03d"}' % i for i in range(40)]
    src.write_text("\n".join(lines) + "\n")
    from modalities_tpu.dataloader.create_index import IndexGenerator

    IndexGenerator(src).create_index(tmp_path / "d.idx")
    outs = []
    for cid in (0, 1):
        out = tmp_path / f"chunk{cid}.jsonl"
        create_shuffled_jsonl_dataset_chunk([src], out, cid, 2, global_seed=5)
        outs.append(out.read_text().splitlines())
    assert len(outs[0]) == len(outs[1]) == 20
    # same seed, different chunk id -> different relative order of their halves
    order0 = [int(line[-5:-2]) for line in outs[0]]
    order1 = [int(line[-5:-2]) - 20 for line in outs[1]]
    assert order0 != order1
