"""SFT acceptance config (BASELINE.md acceptance config 3: SFT with packed
sequences): disjoint-window packed dataset + loss-masking collator, driven through
the full app. The oracle checks the masking is OBSERVABLE (targets outside the
[<b_inc>, <e_inc>] spans are the ignore index) and that training runs to target
with finite decreasing loss."""

import json
from pathlib import Path

import numpy as np
import pytest

from modalities_tpu.main import Main

CONFIG = Path(__file__).parent.parent.parent / "configs" / "config_sft_loss_masked.yaml"

SEQ = 64
B_ID, E_ID = 250, 251


def _build_tokenizer_dir(dst: Path) -> None:
    """Tiny WordLevel HF tokenizer, fully offline, whose vocab carries the span
    markers at the ids the packed stream uses."""
    from tests.conftest import make_word_level_tokenizer

    vocab = {f"tok{i}": i for i in range(250)}
    vocab["<b_inc>"] = B_ID
    vocab["<e_inc>"] = E_ID
    vocab["<pad>"] = 252
    make_word_level_tokenizer(vocab, dst, unk_token="<pad>", pad_token="<pad>")


@pytest.fixture
def sft_workdir(tmp_path, monkeypatch):
    from modalities_tpu.dataloader.packed_data import write_pbin_file

    (tmp_path / "data").mkdir()
    rng = np.random.default_rng(3)
    # 600 docs of exactly SEQ tokens: disjoint windows (reuse_last_target: false)
    # align 1:1 with docs, so every window carries one balanced marker span
    docs = []
    for _ in range(600):
        doc = rng.integers(0, 250, size=SEQ)
        doc[10] = B_ID
        doc[50] = E_ID
        docs.append(doc)
    write_pbin_file(
        tmp_path / "data" / "sft_data.pbin",
        iter([np.concatenate(docs)]),
        token_size_in_bytes=2,
    )
    _build_tokenizer_dir(tmp_path / "data" / "tokenizer")
    monkeypatch.chdir(tmp_path)
    return tmp_path


def test_sft_loss_masked_config_trains(sft_workdir):
    main = Main(
        CONFIG,
        experiments_root_path=sft_workdir / "data" / "experiments",
        experiment_id="sft_e2e",
    )
    components = main.build_components()

    # the built collator masks: one real batch has ignore-index positions outside
    # the span and real targets inside it
    batch = next(iter(components.train_dataloader))
    t = np.asarray(batch.targets["target_ids"])
    assert (t == -100).any(), "loss masking produced no ignored positions"
    assert (t != -100).any(), "loss masking ignored everything"
    # per row: positions after <e_inc> are masked; span interior is kept
    row = t[0]
    kept = np.flatnonzero(row != -100)
    # collator shifts by one: kept span interior lies strictly inside (10, 50)
    assert kept.min() >= 10 and kept.max() <= 49, (kept.min(), kept.max())

    main.run(components)

    results = sft_workdir / "data" / "experiments" / "sft_e2e" / "evaluation_results.jsonl"
    train = [json.loads(line) for line in results.read_text().splitlines() if '"train"' in line]
    assert train[-1]["num_train_steps_done"] == 8
    losses = [r["losses"]["train loss avg"] for r in train]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses
