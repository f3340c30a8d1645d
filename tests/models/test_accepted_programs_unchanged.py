"""The five configurations the benchmark had before PR 40 (and, since PR 43, the sixth, which PR 40 added) trace the program they traced: with `cca_config`,
`scale_residual_merge`, `partial_rotary_factor` and the router of kind `mlp` all unset, the parameter tree and the lowered
forward-and-backward program of each, at toy size, are what the parent commit gave, array for array. The layer scan of
such a model carries the activations alone over no input; a block takes one argument; `apply_rope` takes its old branch.

The digests were taken from `git archive 9565b4e` (the commit PR 40 started from) with this file's own `described`, and
are the same on PR 40's tree. A later PR that changes one of these programs on purpose replaces its digest here, and says so.

PR 43 (one attention ladder in `ops/attention.py`, one rule in `ops/tiers.py`) added the sixth digest, `zaya1-8b-ep2`, taken from
`git archive 1d85786` (the commit it started from) before the three mixers' ladders moved, so that all three are pinned; the five
stood as they were.

PR 41 (a `full`-remat block keeps the flash kernel's o and lse) moved none of the five: what is kept is decided where the train
step is traced (`spec.remat_keep_flash`, False on a model nobody planned for), and off the TPU no block holds a kernel call."""

import hashlib
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
import yaml
from flax.core import meta

from benchmark.weights_hybrid import resolved
from modalities_tpu.models.gpt2.gpt2_model import GPT2LLM, GPT2LLMConfig
from tests.benchmark.toy import TOY_SEQ, make_toy_root
from tests.benchmark.toy_cca_moe import make_toy_cca_moe_root
from tests.benchmark.toy_hybrid import make_toy_hybrid_root
from tests.benchmark.toy_looped import make_toy_looped_root
from tests.benchmark.toy_moe import make_toy_moe_root
from tests.benchmark.toy_swa_moe import make_toy_swa_moe_root

# every configuration with the maker of its own toy root (the plain one cuts the dense decoder alone to a model that builds)
ROOTS = {"modalities-2p7b-d6": make_toy_root, "jamba2-3b-d14": make_toy_hybrid_root, "kanana2-30b-a3b-d9": make_toy_moe_root,
         "ouro-2p6b-t4": make_toy_looped_root, "mellum2-12b-a2p5b-d12": make_toy_swa_moe_root, "zaya1-8b-ep2": make_toy_cca_moe_root}

DIGESTS = {
    "jamba2-3b-d14": {"tree": "9b8b851d3d8af70d", "leaves": 45, "operations": 5196, "program": "6353c2870cff0a05"},
    "kanana2-30b-a3b-d9": {"tree": "f9875164f14d1fed", "leaves": 28, "operations": 3187, "program": "54d4a85ab6eaccb8"},
    "mellum2-12b-a2p5b-d12": {"tree": "015f795054da947e", "leaves": 23, "operations": 3510, "program": "cb006d4edde61b15"},
    "modalities-2p7b-d6": {"tree": "993770db743a2e84", "leaves": 12, "operations": 816, "program": "6bae6b8f26550a9c"},
    "ouro-2p6b-t4": {"tree": "5ea0cce5d8a1517b", "leaves": 16, "operations": 1290, "program": "41b802438489e1d9"},
    "zaya1-8b-ep2": {"tree": "2691cd61a4ab909f", "leaves": 35, "operations": 3171, "program": "89eec99a83418209"},
}


def described(config_dir: Path) -> dict:
    """The toy model of one configuration: its parameter tree (paths, shapes, dtypes) and its lowered program, hashed."""
    raw = yaml.safe_load((config_dir / "train.yaml").read_text())
    config = resolved(raw["model_raw"]["config"], raw)
    model = GPT2LLM(**GPT2LLMConfig(**config).model_dump()).with_spec_updates(remat_variant="full")
    params = jax.eval_shape(lambda: meta.unbox(model.init_params(jax.random.PRNGKey(0))))
    tree = sorted((jax.tree_util.keystr(path), tuple(leaf.shape), str(leaf.dtype)) for path, leaf in jax.tree_util.tree_leaves_with_path(params))
    tokens = jax.ShapeDtypeStruct((2, TOY_SEQ), jnp.int32)

    def loss(p, t):
        out, counted = model.apply_counted(p, {"input_ids": t}, train=True, hidden=True)
        out = out["exits"] if isinstance(out, dict) else out
        return out.astype(jnp.float32).mean() + sum(jnp.sum(v) for v in counted.values())

    text = jax.jit(jax.grad(loss)).lower(params, tokens).as_text()
    # without the numbering of values and of functions (`%123`, `%iterArg_45`, `@_where_363`), which counts what was traced before
    # in the process, and as the bag of its lines: the same operations on the same arrays, in whatever order they are numbered
    lines = sorted(re.sub(r"@([A-Za-z_]+?)_?\d+\b", r"@\1", re.sub(r"%[A-Za-z_]*\d*(#\d+)?(:\d+)?", "%", line)).strip() for line in text.splitlines())
    digest = lambda what: hashlib.sha256(what.encode()).hexdigest()[:16]  # noqa: E731
    return {"tree": digest(json.dumps(tree)), "leaves": len(tree), "operations": len(lines), "program": digest("\n".join(lines))}


@pytest.mark.parametrize("config", sorted(ROOTS))
def test_an_accepted_configuration_lowers_to_the_program_it_lowered_to(tmp_path, config):
    assert described(ROOTS[config](tmp_path / "toy") / "benchmark" / "configs" / config) == DIGESTS[config]


def test_the_layer_scan_of_such_a_model_carries_the_activations_alone(tmp_path):
    root = make_toy_moe_root(tmp_path / "toy")
    """Written out for the expert cell: one loop a run whose carried arrays are the activations' (and the loop's own counter), no
    float32 `[B, S, R]` state beside them, and no per-layer index fed in."""
    raw = yaml.safe_load((root / "benchmark" / "configs" / "kanana2-30b-a3b-d9" / "train.yaml").read_text())
    model = GPT2LLM(**GPT2LLMConfig(**resolved(raw["model_raw"]["config"], raw)).model_dump())
    assert model.config_spec.router_state_width == 0 and not model.config_spec.scale_residual_merge and model.config_spec.cca is None
    params = jax.eval_shape(lambda: meta.unbox(model.init_params(jax.random.PRNGKey(0))))
    jaxpr = jax.make_jaxpr(lambda p, t: model.apply(p, {"input_ids": t})["logits"])(params, jax.ShapeDtypeStruct((2, TOY_SEQ), jnp.int32))
    scans = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "scan"]
    assert len(scans) == len(model.config_spec.stack_runs) == 2
    for eqn in scans:
        carried = [v.aval for v in eqn.invars[eqn.params["num_consts"]: eqn.params["num_consts"] + eqn.params["num_carry"]]]
        assert [(a.shape, str(a.dtype)) for a in carried] == [((2, TOY_SEQ, 128), "bfloat16")], carried
