"""Static closure: every MODALITIES_TPU_* environment variable the code reads
must be documented by its FULL name in docs/components.md's environment-variable
reference. An undocumented knob is an ops hazard — it changes behavior on a pod
without appearing in any runbook. And the other way round: a variable the
reference documents is read somewhere, and the switches PR 43 removed are gone
from the program, its scripts, its documents and its configs."""

import re
from pathlib import Path

REPO = Path(__file__).parent.parent
ENV_VAR = re.compile(r"MODALITIES_TPU_[A-Z0-9_]+")


def _vars_in(text: str) -> set[str]:
    return set(ENV_VAR.findall(text))


def test_every_env_var_read_by_the_code_is_documented():
    code_vars: dict[str, str] = {}
    for path in sorted((REPO / "modalities_tpu").rglob("*.py")):
        for var in _vars_in(path.read_text()):
            code_vars.setdefault(var, str(path.relative_to(REPO)))
    assert code_vars, "env-var scan found nothing — repo layout changed?"

    doc_vars = _vars_in((REPO / "docs" / "components.md").read_text())
    missing = {v: where for v, where in code_vars.items() if v not in doc_vars}
    assert not missing, (
        "environment variables read by the code but absent from "
        f"docs/components.md: {missing}"
    )


def _reference_rows() -> str:
    """The first cell of every row of docs/components.md's environment-variable reference tables (it starts with a variable's name)."""
    doc = (REPO / "docs" / "components.md").read_text()
    return "\n".join(line.split("|")[1] for line in doc.splitlines() if line.startswith("| `MODALITIES_TPU_"))


def test_every_env_var_the_reference_documents_is_read_somewhere():
    """A row that outlives its variable tells an operator to set a switch nothing reads. The readers are the package and
    what ships beside it: the scripts, the two entry scripts, and the tests' own conftest (the durations artifact)."""
    documented = _vars_in(_reference_rows())
    assert len(documented) > 20, "the reference tables were not found — did docs/components.md change its layout?"
    readers = [*sorted((REPO / "modalities_tpu").rglob("*.py")), *sorted((REPO / "scripts").rglob("*.py")),
               REPO / "__graft_entry__.py", REPO / "chip_smoke.py", REPO / "tests" / "conftest.py"]
    read = set().union(*(_vars_in(path.read_text()) for path in readers))
    assert not documented - read, f"documented in docs/components.md and read nowhere: {sorted(documented - read)}"


# What PR 43 removed: five switches that forced a kernel on or off, seven that stood in front of the tuning table, and the
# config field beside the first. One rule (`ops/tiers.py`) says which form runs; a table under MODALITIES_TPU_TUNE_DIR gives blocks.
REMOVED = ("MODALITIES_TPU_FUSED_CE", "MODALITIES_TPU_FUSED_RMSNORM", "MODALITIES_TPU_QUANT_MATMUL", "MODALITIES_TPU_MOE_COMBINE",
           "MODALITIES_TPU_RING_IMPL", "MODALITIES_TPU_FLASH_BLOCK_Q", "MODALITIES_TPU_FLASH_BLOCK_K", "MODALITIES_TPU_CE_BLOCK_ROWS",
           "MODALITIES_TPU_CE_BLOCK_VOCAB", "MODALITIES_TPU_RMSNORM_BLOCK_ROWS", "MODALITIES_TPU_QUANT_MM_BLOCK_M",
           "MODALITIES_TPU_QUANT_MM_BLOCK_N", "lm_head_fused_ce")


def test_the_removed_switches_occur_nowhere():
    assert len(REMOVED) == 13
    where = [REPO / "__graft_entry__.py", *(path for top in ("modalities_tpu", "scripts", "docs", "configs")
                                            for path in sorted((REPO / top).rglob("*")) if path.suffix in (".py", ".md", ".yaml", ".yml", ".json"))]
    assert len(where) > 200
    found = {}
    for path in where:
        text = path.read_text()
        for name in REMOVED:
            if re.search(rf"{name}(?![A-Z0-9_])", text):
                found.setdefault(name, []).append(str(path.relative_to(REPO)))
    assert not found, found


def test_a_yaml_that_still_carries_the_removed_config_field_is_refused_as_any_unknown_key_is():
    """A config that still carries the field PR 43 took away (the last of `REMOVED`) does not train a model that quietly
    ignores it: the component factory refuses it by name, as it refuses every key a config does not know, before
    anything is built; and the model's constructor does not take it either."""
    import pytest
    from pydantic import BaseModel

    from modalities_tpu.config.component_factory import ComponentFactory
    from modalities_tpu.config.pydantic_if_types import PydanticModelIFType
    from modalities_tpu.registry.components import COMPONENTS
    from modalities_tpu.registry.registry import Registry
    from tests.models.test_gpt2_model import tiny_gpt2

    class Holder(BaseModel):
        model: PydanticModelIFType

    field = REMOVED[-1]
    norm = {"norm_type": "rms_norm", "config": {"ndim": 128, "bias": False}}
    config = dict(
        sample_key="input_ids", prediction_key="logits", poe_type="NOPE", sequence_length=32, vocab_size=128, n_layer=2, n_head_q=4,
        n_head_kv=2, n_embd=128, ffn_hidden=128, dropout=0.0, bias=False, attention_implementation="manual", activation_type="swiglu",
        attention_config={"qkv_transforms": [{"type_hint": "RotaryTransform", "config": {"n_embd": 128, "n_head": 4, "base_freq": 10000}}]},
        attention_norm_config=norm, ffn_norm_config=norm, lm_head_norm_config=norm, use_weight_tying=True, lm_head_chunk_size=8,
    )
    block = lambda **more: {"model": {"component_key": "model", "variant_key": "gpt2", "config": {**config, **more}}}  # noqa: E731
    factory = ComponentFactory(Registry(COMPONENTS))
    assert factory.build_components(block(), Holder).model.config_spec.lm_head_chunk_size == 8  # the config without the key builds
    with pytest.raises(ValueError, match=rf"Invalid keys \['{field}'\] for config `model.gpt2`"):
        factory.build_components(block(**{field: "auto"}), Holder)
    with pytest.raises(TypeError, match=field):
        tiny_gpt2(**{field: "on"})
