"""Test harness: run everything on CPU with 8 virtual devices so mesh/sharding logic
(dp/tp/pp/cp) is exercised without TPU hardware (SURVEY.md §4 TPU translation)."""

import os

# Force the CPU, with 8 virtual devices for the sharding logic: tests are fast and
# deterministic there, and a test process must never take the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (xla_flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

# --- slowest-test artifact (PR 13) ---------------------------------------------
# Past slow-marking rebalances (PRs 8/9/11) eyeballed `--durations` output from a
# scrollback; this hook writes the top N call-phase durations to a JSONL artifact
# at session end so the next rebalance is data-driven. Path override:
# MODALITIES_TPU_TEST_DURATIONS_PATH ("" disables). Workers under pytest-xdist
# skip the write (each would clobber the file with a partial view).

_DURATIONS_TOP_N = 15
_durations: dict = {}


def pytest_runtest_logreport(report):
    if report.when == "call":
        _durations[report.nodeid] = report.duration


def pytest_sessionfinish(session, exitstatus):
    if hasattr(session.config, "workerinput"):  # xdist worker: partial view
        return
    raw = os.environ.get("MODALITIES_TPU_TEST_DURATIONS_PATH")
    if raw == "":
        return
    path = raw or str(session.config.rootpath / "test_durations.jsonl")
    try:
        import json

        slowest = sorted(_durations.items(), key=lambda kv: kv[1], reverse=True)
        with open(path, "w") as f:
            for nodeid, duration in slowest[:_DURATIONS_TOP_N]:
                f.write(json.dumps({"nodeid": nodeid, "duration_s": round(duration, 3)}) + "\n")
    except OSError:
        pass  # an unwritable artifact path must never fail the suite


@pytest.fixture
def kernels_interpreted():
    """What this test traces takes every kernel a TPU would take, interpreted (`ops/tiers.interpreted_kernels`)."""
    from modalities_tpu.ops import tiers

    with tiers.interpreted_kernels():
        yield


@pytest.fixture
def tune_table(tmp_path, monkeypatch):
    """`tune_table({"flash_attention|*|*": {"block_q": 64, "block_k": 32}})`: this test's kernels take their blocks from
    a table under MODALITIES_TPU_TUNE_DIR, which is how an operator gives a kernel other blocks than the shipped ones."""
    from modalities_tpu.ops.pallas import autotune

    def plant(entries):
        monkeypatch.setenv(autotune.TUNE_DIR_ENV, str(tmp_path / "tune"))
        autotune.save_table(tmp_path / "tune", autotune.device_kind_slug(), entries)
        autotune.clear_cache()

    yield plant
    autotune.clear_cache()


@pytest.fixture
def tmp_experiment_dir(tmp_path):
    d = tmp_path / "experiments"
    d.mkdir()
    return d


def make_word_level_tokenizer(vocab: dict, dst, unk_token: str, **special_tokens):
    """Tiny offline WordLevel HF tokenizer saved to `dst` — the shared builder for
    every test that needs a tokenizer without hub access (sft/generate/conversion/
    instruction-tuning e2e). `special_tokens` forwards to PreTrainedTokenizerFast
    (eos_token=..., pad_token=..., bos_token=...)."""
    tokenizers = pytest.importorskip("tokenizers")
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace
    from transformers import PreTrainedTokenizerFast

    tok = tokenizers.Tokenizer(WordLevel(vocab, unk_token=unk_token))
    tok.pre_tokenizer = Whitespace()
    fast = PreTrainedTokenizerFast(tokenizer_object=tok, **special_tokens)
    fast.save_pretrained(dst)
    return fast
