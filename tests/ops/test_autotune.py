"""Per-device kernel autotune table: round-trip, lookup precedence, dispatch
observability, shipped defaults, and the smoke sweep."""

import json

import pytest

from modalities_tpu.ops.pallas import autotune


@pytest.fixture(autouse=True)
def _fresh_cache():
    autotune.clear_cache()
    yield
    autotune.clear_cache()


def test_shape_bucket_pow2_ceiling():
    assert autotune.shape_bucket(1024) == "1024"
    assert autotune.shape_bucket(1025) == "2048"
    assert autotune.shape_bucket(21, 200) == "32x256"


@pytest.mark.parametrize(
    "kind,slug",
    [
        ("TPU v6e", "v6e"),
        ("TPU v6 lite", "v6e"),
        ("TPU v5p", "v5p"),
        ("TPU v5e", "v5e"),
        ("TPU v5 lite", "v5e"),
        ("TPU v4", "v4"),
        ("Some Future Chip 9000", "some_future_chip_9000"),
    ],
)
def test_device_kind_slug(kind, slug):
    assert autotune.device_kind_slug(kind) == slug


def test_save_and_lookup_round_trip(tmp_path, monkeypatch):
    """A sweep writes; a 'fresh process' (cleared cache) loads the same answer."""
    monkeypatch.setenv(autotune.TUNE_DIR_ENV, str(tmp_path))
    path = autotune.save_table(
        tmp_path, "v5e", {"fused_ce|n4096_v16384_e1024|bfloat16": {"block_rows": 512, "block_vocab": 1024}}
    )
    assert path == tmp_path / "v5e.json"
    autotune.clear_cache()  # simulate a fresh process
    hit = autotune.lookup("fused_ce", "n4096_v16384_e1024", "bfloat16", device_kind="TPU v5e")
    assert hit == {"block_rows": 512, "block_vocab": 1024}


def test_save_table_merges_existing_entries(tmp_path):
    autotune.save_table(tmp_path, "v5e", {"a|*|*": {"x": 1}})
    autotune.save_table(tmp_path, "v5e", {"b|*|*": {"y": 2}})
    raw = json.loads((tmp_path / "v5e.json").read_text())
    assert raw["entries"] == {"a|*|*": {"x": 1}, "b|*|*": {"y": 2}}


def test_lookup_probe_order_exact_beats_wildcard(tmp_path, monkeypatch):
    monkeypatch.setenv(autotune.TUNE_DIR_ENV, str(tmp_path))
    autotune.save_table(
        tmp_path,
        "v5e",
        {
            "fused_ce|*|*": {"block_rows": 1},
            "fused_ce|*|bfloat16": {"block_rows": 2},
            "fused_ce|n64|*": {"block_rows": 3},
            "fused_ce|n64|bfloat16": {"block_rows": 4},
        },
    )
    look = lambda b, d: autotune.lookup("fused_ce", b, d, device_kind="TPU v5e")
    assert look("n64", "bfloat16") == {"block_rows": 4}
    assert look("n64", "float32") == {"block_rows": 3}
    assert look("n128", "bfloat16") == {"block_rows": 2}
    assert look("n128", "float32") == {"block_rows": 1}


def test_tune_dir_beats_shipped_table(tmp_path, monkeypatch):
    monkeypatch.setenv(autotune.TUNE_DIR_ENV, str(tmp_path))
    autotune.save_table(tmp_path, "v5e", {"flash_attention|*|*": {"block_q": 256, "block_k": 256}})
    hit = autotune.lookup("flash_attention", "sq2048_sk2048", "bfloat16", device_kind="TPU v5e")
    assert hit == {"block_q": 256, "block_k": 256}


def test_shipped_v5e_defaults_reproduce_flash_choice(monkeypatch):
    """The one empirically-tuned config (1.3B / seq-2048 / v5e, ops/attention.py)
    must come back out of the shipped table."""
    monkeypatch.delenv(autotune.TUNE_DIR_ENV, raising=False)
    hit = autotune.lookup("flash_attention", "sq2048_sk2048", "bfloat16", device_kind="TPU v5e")
    assert hit == {"block_q": 1024, "block_k": 1024}
    for kind in ("TPU v5p", "TPU v6e"):
        assert autotune.lookup("fused_ce", "whatever", "bfloat16", device_kind=kind)


def test_corrupt_table_degrades_to_none(tmp_path, monkeypatch):
    monkeypatch.setenv(autotune.TUNE_DIR_ENV, str(tmp_path))
    (tmp_path / "cpu.json").write_text("{not json")
    warnings = []
    monkeypatch.setattr(autotune.logger, "warning", lambda msg, *a: warnings.append(msg))
    assert autotune.lookup("fused_ce", "n64", "float32", device_kind="cpu") is None
    assert autotune.lookup("fused_ce", "n64", "float32", device_kind="cpu") is None
    assert sum("unreadable tuning table" in w for w in warnings) == 1  # warn once


def test_missing_table_is_silent_none(tmp_path, monkeypatch):
    monkeypatch.setenv(autotune.TUNE_DIR_ENV, str(tmp_path))
    assert autotune.lookup("fused_ce", "n64", "float32", device_kind="TPU v9x") is None


# ---------------------------------------------------------- dispatch plumbing


def _fake_cpu_table(tmp_path, entries):
    """The CPU test host resolves to slug 'cpu'; plant a table for it."""
    slug = autotune.device_kind_slug()  # whatever this host's jax device reports
    autotune.save_table(tmp_path, slug, entries)


@pytest.fixture
def tables(tmp_path, monkeypatch, tune_table):
    """The two places a table can lie, both empty: `plant("shipped" | "operator", entries)` puts one there
    (the shipped directory is the package's own; the operator's is `MODALITIES_TPU_TUNE_DIR`: conftest's `tune_table`)."""
    monkeypatch.setattr(autotune, "SHIPPED_TABLE_DIR", tmp_path / "shipped")
    tune_table({})

    def plant(where, entries):
        if where == "operator":
            return tune_table(entries)
        _fake_cpu_table(tmp_path / "shipped", entries)
        autotune.clear_cache()

    return plant


def test_ce_blocks_operator_table_beats_shipped_beats_default(tables):
    from modalities_tpu.ops.cross_entropy import DEFAULT_BLOCK_ROWS, DEFAULT_BLOCK_VOCAB, resolve_ce_blocks

    assert resolve_ce_blocks(4096, 16384, 1024, "bfloat16") == (DEFAULT_BLOCK_ROWS, DEFAULT_BLOCK_VOCAB)
    tables("shipped", {"fused_ce|*|*": {"block_rows": 64, "block_vocab": 1024}})
    assert resolve_ce_blocks(4096, 16384, 1024, "bfloat16") == (64, 1024)
    # a size the winning entry lacks is the caller's default, not the next table's
    tables("operator", {"fused_ce|*|*": {"block_rows": 32}})
    assert resolve_ce_blocks(4096, 16384, 1024, "bfloat16") == (32, DEFAULT_BLOCK_VOCAB)


def test_flash_blocks_operator_table_beats_shipped_beats_default(tables):
    from modalities_tpu.ops.pallas.flash_attention import flash_blocks

    assert flash_blocks(2048, 2048, "bfloat16") == (1024, 1024)
    tables("shipped", {"flash_attention|*|*": {"block_q": 512, "block_k": 256}})
    assert flash_blocks(2048, 2048, "bfloat16") == (512, 256)
    assert flash_blocks(2048, 2048, "bfloat16", backward=True) == (512, 256)  # no entry of the backward's own: the forward's
    tables("operator", {"flash_attention|*|*": {"block_q": 128, "block_k": 256}, "flash_attention_bwd|*|*": {"block_q": 256, "block_k": 128}})
    assert flash_blocks(2048, 2048, "bfloat16") == (128, 256)
    assert flash_blocks(2048, 2048, "bfloat16", backward=True) == (256, 128)
    # blocks still step down to divide short sequences
    bq, bk = flash_blocks(48, 48, "float32")
    assert 48 % bq == 0 and 48 % bk == 0


def test_rmsnorm_block_operator_table_beats_shipped_beats_default(tables):
    from modalities_tpu.ops.rmsnorm import DEFAULT_BLOCK_ROWS, resolve_rmsnorm_block_rows

    assert resolve_rmsnorm_block_rows(1024, "bfloat16") == DEFAULT_BLOCK_ROWS
    tables("shipped", {"fused_rmsnorm|*|*": {"block_rows": 128}})
    assert resolve_rmsnorm_block_rows(1024, "bfloat16") == 128
    tables("operator", {"fused_rmsnorm|*|*": {"block_rows": 16}})
    assert resolve_rmsnorm_block_rows(1024, "bfloat16") == 16


# ------------------------------------------------------------------ the sweep


def test_smoke_sweep_round_trips_and_publishes_spans(tmp_path, monkeypatch):
    from modalities_tpu.telemetry.spans import SpanRecorder

    monkeypatch.setenv(autotune.TUNE_DIR_ENV, str(tmp_path))
    seen = []
    recorder = SpanRecorder(on_record=lambda rec: seen.append(rec.name))
    summary = autotune.tune_kernels(tmp_path, iters=1, recorder=recorder, smoke=True)

    assert summary["interpret"] is True  # CPU host => interpret sweep
    for kernel in ("flash_attention", "fused_ce", "fused_rmsnorm", "quant_matmul"):
        assert any(k.startswith(f"{kernel}|") for k in summary["entries"]), kernel
        assert any(name.startswith(f"tune/{kernel}/") for name in seen), kernel

    # fresh process: the written table answers lookups with the measured winner
    autotune.clear_cache()
    key = next(k for k in summary["entries"] if k.startswith("fused_ce|"))
    _, bucket, dtype = key.split("|")
    assert autotune.lookup("fused_ce", bucket, dtype) == summary["entries"][key]
