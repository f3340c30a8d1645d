"""The whole donated train step of the gated-delta-rule cell compiled for a described v5e, and its `memory_analysis()` held to
`meta.json`: `tests/ops/test_tpu_compile.py`'s helper and topology, in a file of its own, under tests/benchmark/ (the first directory the suite collects), so that a worker of the suite
takes this one long compile (a minute) at the start of the run and not at its end, where `tests/ops/` falls (the suite.s clock: `--dist loadfile` deals files out whole, and the last file started is the run.s tail)."""

import os
import re

from tests.benchmark.accepted import holds_at_least
from tests.ops.test_tpu_compile import _compiled_cell_step, v5e  # noqa: F401  (the described topology's fixture)


def test_the_gated_delta_rule_cells_step_compiles_for_v5e_and_fits_as_meta_json_says(v5e, monkeypatch, tmp_path):
    """The whole donated train step of `benchmark/configs/qwen3-next-80b-a3b-d4/train.yaml` (PR 44: three gated-delta-rule layers
    and one gated attention layer of width 2048, 64 of 512 experts of 512 held beside a gated shared expert, 18,992 rows of the
    untied table, one row of 16,384, every block rematerialized) compiled for a described v5e: the chunked rule, the convolution,
    the zero-centred norms through the fused norm kernel, the flash kernels at heads of 256 with the FUSED backward at the table's
    own blocks, the expert layer's gathers (64 held to 10 choices: over `combine_plan`'s line); and the compiler's peak is the
    one `meta.json` records for the step as it stands, under the chip's 15.75 GiB. The six kernels the cell was accepted with are
    among the step's (`holds_at_least`: PR 45 and PR 46 gave the rule's walk and the mixer's head norms kernels of their own)."""
    import json

    text, peak = _compiled_cell_step(v5e, monkeypatch, tmp_path, "qwen3-next-80b-a3b-d4", 18992, 16384)
    kernels = sorted(set(re.findall(r"(\w+)\)*/pallas_call", text)))
    assert holds_at_least(kernels, {"flash_attention_bwd", "flash_attention_fwd", "fused_ce_bwd_dw", "fused_ce_fwd", "fused_rmsnorm_bwd", "fused_rmsnorm_fwd"}), kernels
    for scope in ("gdn/in_proj", "gdn/conv", "gdn/gates", "gdn/qk_norm", "gdn/rule", "intra", "gdn/out_norm", "gdn/out", "attn/gate", "moe/shared_gate"):
        assert f"/{scope}/" in text, scope
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    meta = json.load(open(os.path.join(repo, "benchmark", "configs", "qwen3-next-80b-a3b-d4", "meta.json")))
    recorded = float(re.search(r"AS THE STEP STANDS[^:]*: ([\d.]+) GiB", meta["memory_analysis"]).group(1))
    assert abs(peak / 2**30 - recorded) < 0.15 and peak < 15.75 * 2**30, (peak / 2**30, recorded)
