"""`ops/attention.causal_attention` holds the ladder that picks a mixer's attention function, once: which leaf
function each configuration reaches (impl x window x dropout x v's width, and the cp axis), counted by the calls, at toy
shapes; what the ladder refuses; and the one question a planner shares with it (`takes_kernel`)."""

import jax
import jax.numpy as jnp
import pytest

from modalities_tpu.ops import attention

LEAVES = ("ring_attention", "manual_attention", "sdpa_attention", "flash")
NARROW = 8  # v's width where it is not q's 16


@pytest.fixture
def reached(monkeypatch):
    """Every leaf replaced by a recorder of its name and of the keywords the rung hands it."""
    import modalities_tpu.parallel.ring_attention as ring

    class Calls(list):
        """The calls in order; `flash` is the recorder a test hands in for the kernel's rung."""

    calls = Calls()

    def leaf(name):
        def record(q, k, v, *args, **kwargs):
            calls.append((name, args, kwargs))
            return jnp.zeros(q.shape[:-1] + v.shape[-1:], q.dtype)
        return record

    monkeypatch.setattr(ring, "ring_attention", leaf("ring_attention"))
    monkeypatch.setattr(attention, "manual_attention", leaf("manual_attention"))
    monkeypatch.setattr(attention, "sdpa_attention", leaf("sdpa_attention"))
    calls.flash = leaf("flash")
    return calls


def qkv(v_width=16):
    q = jnp.ones((1, 8, 2, 16))
    return q, q, jnp.ones((1, 8, 2, v_width))


RNG = jax.random.PRNGKey(0)
RUNGS = [
    # impl, window, dropout, v's width -> the leaf, its positional arguments after q, k, v and its keywords
    ("dao_flash", None, 0.0, 16, "flash", (None,), {}),
    ("dao_flash", 4, 0.0, 16, "flash", (4,), {}),
    ("dao_flash", None, 0.0, NARROW, "flash", (None,), {}),  # latent attention: the kernels read both widths off the arrays
    ("manual", None, 0.0, 16, "manual_attention", (), {"window": None}),
    ("manual", 4, 0.0, 16, "manual_attention", (), {"window": 4}),
    ("manual", None, 0.0, NARROW, "manual_attention", (), {"window": None}),
    ("pytorch_flash", None, 0.0, 16, "sdpa_attention", (), {}),
    ("pytorch_flash", 4, 0.0, 16, "manual_attention", (), {"window": 4}),  # fused SDPA's mask is causal, no more
    ("pytorch_flash", None, 0.0, NARROW, "manual_attention", (), {"window": None}),  # SDPA takes one width for q, k and v
    ("manual", None, 0.1, 16, "manual_attention", (), {"dropout_rate": 0.1, "dropout_rng": RNG, "window": None}),
    ("manual", 4, 0.1, 16, "manual_attention", (), {"dropout_rate": 0.1, "dropout_rng": RNG, "window": 4}),
    ("pytorch_flash", None, 0.1, 16, "manual_attention", (), {"dropout_rate": 0.1, "dropout_rng": RNG, "window": None}),  # SDPA has no dropout hook
    ("pytorch_flash", None, 0.1, NARROW, "manual_attention", (), {"dropout_rate": 0.1, "dropout_rng": RNG, "window": None}),
]


@pytest.mark.parametrize("impl, window, dropout, v_width, leaf, args, kwargs", RUNGS,
                         ids=[f"{impl}-window_{window}-dropout_{dropout}-v{v_width}" for impl, window, dropout, v_width, *_ in RUNGS])
def test_every_configuration_reaches_one_leaf_with_what_the_rung_hands_it(reached, impl, window, dropout, v_width, leaf, args, kwargs):
    out = attention.causal_attention(*qkv(v_width), impl=impl, window=window, dropout_rate=dropout, dropout_rng=RNG if dropout else None,
                                     flash=reached.flash)
    assert out.shape == (1, 8, 2, v_width)
    (name, got_args, got_kwargs), = reached
    assert (name, got_args) == (leaf, args)
    assert got_kwargs.keys() == kwargs.keys() and all(got_kwargs[key] is kwargs[key] or got_kwargs[key] == kwargs[key] for key in kwargs if key != "dropout_rng")


def test_the_kernels_rung_is_told_kept_only_where_the_block_keeps(reached):
    """A call that is not told is the call it always was: `tests/benchmark/` wraps the rung with a function that has no such keyword."""
    attention.causal_attention(*qkv(), impl="dao_flash", window=4, kept=True, flash=reached.flash)
    attention.causal_attention(*qkv(), impl="dao_flash", kept=False, flash=lambda q, k, v, window=None: reached.flash(q, k, v, window))
    assert [(name, args, kwargs) for name, args, kwargs in reached] == [("flash", (4,), {"kept": True}), ("flash", (None,), {})]


@pytest.mark.parametrize("impl", ["manual", "pytorch_flash", "dao_flash"])
def test_under_a_cp_axis_every_implementation_is_the_ring(reached, impl, monkeypatch):
    import modalities_tpu.running_env.device_mesh as device_mesh

    monkeypatch.setattr(device_mesh, "current_mesh", lambda: "the mesh")
    attention.causal_attention(*qkv(), impl=impl, cp_axis="cp", flash=reached.flash)
    assert reached == [("ring_attention", ("the mesh",), {"axis_name": "cp"})]


@pytest.mark.parametrize("keys, message", [
    ({"impl": "manual", "cp_axis": "cp", "dropout_rate": 0.1}, "not implemented for ring attention"),
    ({"impl": "dao_flash", "cp_axis": "cp", "dropout_rate": 0.1}, "not implemented for ring attention"),
    ({"impl": "dao_flash", "dropout_rate": 0.1}, "not implemented in the dao_flash Pallas kernel"),
])
def test_the_ladder_refuses_dropout_where_no_function_samples(reached, keys, message):
    with pytest.raises(NotImplementedError, match=message):
        attention.causal_attention(*qkv(), dropout_rng=RNG, flash=reached.flash, **keys)
    assert reached == []


def test_a_planner_asks_the_ladders_own_question():
    """`GPT2LLM.remat_flash_calls` counts kernel calls by `takes_kernel`, the comparison the ladder's third rung makes."""
    assert attention.takes_kernel("dao_flash") and attention.takes_kernel(attention.AttentionImplementation.DAO_FLASH.value)
    assert not attention.takes_kernel("manual") and not attention.takes_kernel("pytorch_flash")
    assert not attention.takes_kernel("dao_flash", dropout_rate=0.1) and not attention.takes_kernel("dao_flash", cp_axis="cp")


def test_the_three_mixers_import_no_attention_function_from_the_model_file():
    """The arrow points down: `mla.py` and `cca.py` call `ops/attention.causal_attention`, and the model file holds no ladder."""
    import inspect

    from modalities_tpu.models.gpt2 import cca, gpt2_model, mla

    for mixer in (cca, mla):
        source = inspect.getsource(mixer)
        assert "causal_attention(" in source
        assert not any(f"{name}(" in source.replace("causal_attention(", "") for name in ("manual_attention", "sdpa_attention", "flash_attention", "masked_attention"))
    model = inspect.getsource(gpt2_model)
    assert model.count("causal_attention(") == 1 and "DAO_FLASH.value" not in model.split("class GPT2LLMConfig")[1].split("def check_dropout_supported")[0]
    assert not hasattr(gpt2_model, "manual_attention") and not hasattr(gpt2_model, "sdpa_attention")
