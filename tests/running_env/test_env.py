"""The compilation cache seam `run`, `warmstart`, `serve` and chip_smoke.py share
(running_env/env.py)."""

from pathlib import Path

import pytest


@pytest.mark.parametrize("from_env", ["/somewhere/outside", None])
def test_compilation_cache_is_placed_from_outside_or_at_one_fixed_path(from_env, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own setting stands and the code
    sets no other; without it, one directory inside the checkout."""
    import jax

    from modalities_tpu.running_env import env

    updates = []
    monkeypatch.setattr(jax.config, "update", lambda name, value: updates.append((name, value)))
    if from_env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", from_env)
    placed = env.configure_compilation_cache()
    repo = Path(__file__).resolve().parents[2]
    if from_env is None:
        assert placed == str(repo / ".jax_compilation_cache")
        assert updates == [("jax_compilation_cache_dir", placed)]
    else:
        assert placed == from_env and updates == []
