"""The compilation cache seam `run`, `warmstart`, `serve` and chip_smoke.py share
(running_env/env.py)."""

from pathlib import Path

import pytest


@pytest.mark.parametrize("from_env", ["/somewhere/outside", None])
def test_compilation_cache_is_placed_from_outside_or_at_one_fixed_path(from_env, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own setting stands and the code
    sets no other; without it, one directory inside the checkout. Either way an
    entry's key takes in the program's metadata, so that a tree that renamed a scope
    never gets back another tree's executable with the old names."""
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
    assert updates[0] == ("jax_compilation_cache_include_metadata_in_key", True)
    if from_env is None:
        assert placed == str(repo / ".jax_compilation_cache")
        assert updates[1:] == [("jax_compilation_cache_dir", placed)]
    else:
        assert placed == from_env and updates[1:] == []


def test_a_scope_only_change_misses_the_cache_only_with_metadata_in_the_key(tmp_path, monkeypatch):
    """The trap `configure_compilation_cache` closes: JAX's default key leaves
    metadata out, so a program that differs from a cached one only by a named scope
    hits that entry and comes back with the cached `op_name`s."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    def program(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.tanh(x) @ x
        return jax.jit(f).lower(jnp.ones((8, 8), jnp.float32))

    settings = {"jax_compilation_cache_dir": str(tmp_path), "jax_persistent_cache_min_compile_time_secs": 0.0,
                "jax_persistent_cache_min_entry_size_bytes": -1, "jax_compilation_cache_include_metadata_in_key": False}
    before = {name: getattr(jax.config, name) for name in settings}
    for name, value in settings.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    try:
        seen = {}
        for flag in (False, True):
            jax.config.update("jax_compilation_cache_include_metadata_in_key", flag)
            program(f"old_scope_{flag}").compile()
            seen[flag] = program(f"new_scope_{flag}").compile().as_text()
        assert "old_scope_False" in seen[False] and "new_scope_False" not in seen[False], "stale names from the hit"
        assert "new_scope_True" in seen[True] and "old_scope_True" not in seen[True]
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
