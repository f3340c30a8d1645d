"""The whole donated train step of the Mamba-2 cell compiled for a described v5e, and its `memory_analysis()` held to
`meta.json`: `tests/ops/test_tpu_compile.py`'s helper and topology, in a file of its own under tests/benchmark/ (the first
directory the suite collects), so that a worker takes this one long compile at the start of the run and not at its end."""

import json
import os
import re

from tests.ops.test_tpu_compile import _compiled_cell_step, v5e  # noqa: F401  (the described topology's fixture)


def test_the_mamba_2_cells_step_compiles_for_v5e_and_fits_as_meta_json_says(v5e, monkeypatch, tmp_path):
    """The whole donated train step of `benchmark/configs/granite-4.0-h-small-d10/train.yaml` (PR 52: nine Mamba-2 layers of 32 held
    heads of 64 with a state of 128 in chunks of 256 and one attention layer of 8 on 2 heads of 128 without positions, width 4096,
    9 of 72 experts of 768 held beside 384 of the shared expert's 1536, 12,544 rows of the tied table, one row of 8,192, every
    block rematerialized) compiled for a described v5e: the chunked form's products, the convolution and the gated norm in plain
    `jax.numpy`, the flash kernels at heads of 128 under a scale of their own, the expert layer's sum by token through the kernel
    `moe_combine` (9 held to 10 choices: under `combine_plan`'s line, the kernel at k 10), the fused cross entropy against the tied
    table at width 4096; and the compiler's peak is the one `meta.json` records, under ISSUE 52's 15.0 GiB."""
    text, peak = _compiled_cell_step(v5e, monkeypatch, tmp_path, "granite-4.0-h-small-d10", 12544, 8192)
    kernels = sorted(set(re.findall(r"(\w+)\)*/pallas_call", text)))
    assert kernels == ["flash_attention_bwd", "flash_attention_fwd", "fused_ce_bwd_dw", "fused_ce_fwd", "fused_rmsnorm_bwd", "fused_rmsnorm_fwd",
                       "moe_combine"], kernels
    for scope in ("ssd/in_proj", "ssd/conv", "ssd/scan", "intra", "state", "ssd/gate", "ssd/out_proj", "attn/attn_core", "moe/shared", "moe/combine"):
        assert f"/{scope}/" in text, scope
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    meta = json.load(open(os.path.join(repo, "benchmark", "configs", "granite-4.0-h-small-d10", "meta.json")))
    recorded = float(re.search(r"AS THE STEP STANDS[^:]*: ([\d.]+) GiB", meta["memory_analysis"]).group(1))
    assert abs(peak / 2**30 - recorded) < 0.15 and peak < 15.0 * 2**30, (peak / 2**30, recorded)
