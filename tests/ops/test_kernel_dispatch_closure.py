"""Static closure check: every Pallas kernel reachable through a dispatch
wrapper must be drivable in interpret mode, so CPU parity tests can always
exercise the real kernel code path (never just the fallback tier).

Pure AST/inspect — no tracing, runs in milliseconds. Below it, the other half of
the dispatch contract: nothing stands behind a kernel on a TPU, and no probe
mistakes a dead backend for a CPU."""

import ast
import inspect
from pathlib import Path

import pytest

import modalities_tpu.ops.pallas as pallas_pkg

PALLAS_DIR = Path(pallas_pkg.__file__).parent


def _pallas_call_sites(tree):
    """Yield (lineno, keywords) for every `pl.pallas_call(...)` / `pallas_call(...)`."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) else getattr(fn, "id", None)
        if name == "pallas_call":
            yield node.lineno, {kw.arg for kw in node.keywords}


def test_every_pallas_call_wires_interpret():
    offenders = []
    found_any = False
    for path in sorted(PALLAS_DIR.glob("*.py")):
        tree = ast.parse(path.read_text())
        for lineno, kwargs in _pallas_call_sites(tree):
            found_any = True
            if "interpret" not in kwargs:
                offenders.append(f"{path.name}:{lineno}")
    assert found_any, "no pallas_call sites found — did the kernels move?"
    assert not offenders, (
        "pallas_call sites without an interpret= kwarg (CPU parity tests could "
        f"only reach the fallback tier): {offenders}"
    )


def test_dispatch_entry_points_expose_interpret():
    """The manifest of kernel entry points reachable from dispatch wrappers.
    A new kernel added to a wrapper without an interpret path must fail here."""
    from modalities_tpu.ops import head_norm as head_norm_dispatch
    from modalities_tpu.ops.cross_entropy import fused_ce_sum_and_count as ce_dispatch
    from modalities_tpu.ops.pallas.flash_attention import pallas_flash_attention
    from modalities_tpu.ops.pallas.fused_ce import fused_ce_sum_and_count
    from modalities_tpu.ops.pallas.fused_rmsnorm import fused_rms_norm
    from modalities_tpu.ops.pallas.head_norm import gated_head_rms_norm, head_l2_norm
    from modalities_tpu.ops.pallas.moe_combine import moe_combine
    from modalities_tpu.ops.pallas.quant_matmul import quant_matmul
    from modalities_tpu.ops.pallas.selective_scan import pallas_selective_scan
    from modalities_tpu.ops.quant_matmul import quant_matmul_or_fallback
    from modalities_tpu.ops.rmsnorm import rms_norm_or_fallback
    from modalities_tpu.ops.selective_scan import selective_scan

    for fn in (pallas_flash_attention, fused_ce_sum_and_count, fused_rms_norm, ce_dispatch, rms_norm_or_fallback, quant_matmul, quant_matmul_or_fallback,
               pallas_selective_scan, selective_scan, moe_combine, head_l2_norm, gated_head_rms_norm, head_norm_dispatch.head_l2_norm,
               head_norm_dispatch.gated_head_rms_norm):
        params = inspect.signature(fn).parameters
        assert "interpret" in params, f"{fn.__module__}.{fn.__name__} lacks an interpret path"
        assert params["interpret"].default is False, fn.__name__


def test_dispatch_wrappers_cover_every_kernel_module():
    """Every kernel module in ops/pallas/ must be imported by some dispatch-tier
    module under ops/ — a kernel nobody dispatches to is dead weight or, worse,
    wired in somewhere that skips the tier pattern."""
    kernel_modules = {
        p.stem for p in PALLAS_DIR.glob("*.py") if p.stem not in ("__init__", "autotune")
    }
    ops_dir = PALLAS_DIR.parent
    imported = set()
    for path in ops_dir.glob("*.py"):
        text = path.read_text()
        for mod in kernel_modules:
            if f"pallas.{mod}" in text:
                imported.add(mod)
    missing = kernel_modules - imported
    assert not missing, f"kernel modules with no dispatch-tier consumer under ops/: {missing}"


# ------------------------------------------------------- no tier behind a kernel


def _call_attention():
    import jax.numpy as jnp

    from modalities_tpu.ops.attention import flash_attention_or_fallback

    q = jnp.ones((1, 16, 2, 8))
    return flash_attention_or_fallback(q, q, q)


def _call_fused_ce():
    import jax.numpy as jnp

    from modalities_tpu.ops.cross_entropy import fused_ce_sum_and_count

    return fused_ce_sum_and_count(jnp.ones((8, 16)), jnp.ones((32, 16)), jnp.zeros((8,), jnp.int32))


def _call_rmsnorm():
    import jax.numpy as jnp

    from modalities_tpu.ops.rmsnorm import rms_norm_or_fallback

    return rms_norm_or_fallback(jnp.ones((8, 16)), jnp.ones((16,)))


def _call_quant_matmul():
    import jax.numpy as jnp

    from modalities_tpu.ops.quant_matmul import quant_matmul_or_fallback

    return quant_matmul_or_fallback(jnp.ones((8, 16)), jnp.ones((16, 8), jnp.int8), jnp.ones((8,)))


def _call_selective_scan():
    import jax.numpy as jnp

    from modalities_tpu.ops.selective_scan import selective_scan

    rows, narrow = jnp.ones((1, 16, 128)), jnp.ones((1, 16, 8))
    return selective_scan(rows, rows, -jnp.ones((128, 8)), narrow, narrow)


def _call_moe_combine():
    import jax.numpy as jnp

    from modalities_tpu.ops.expert_dispatch import routed_experts

    x, stack = jnp.ones((16, 128)), jnp.ones((2, 128, 128))
    return routed_experts(x, jnp.zeros((16, 1), jnp.int32), jnp.ones((16, 1)), stack, stack, stack, offset=0, combine="slabs")


def _call_head_l2_norm():
    import jax.numpy as jnp

    from modalities_tpu.ops.head_norm import head_l2_norm

    return head_l2_norm(jnp.ones((1, 16, 2, 128)))


def _call_gated_head_rms_norm():
    import jax.numpy as jnp

    from modalities_tpu.ops.head_norm import gated_head_rms_norm

    rows = jnp.ones((1, 16, 2, 128))
    return gated_head_rms_norm(rows, rows, jnp.ones((128,)), eps=1e-6)


@pytest.mark.parametrize(
    "call",
    [_call_moe_combine, _call_selective_scan, _call_attention, _call_fused_ce, _call_rmsnorm, _call_quant_matmul, _call_head_l2_norm,
     _call_gated_head_rms_norm],
)
def test_dispatcher_raises_what_the_kernel_raises_on_a_tpu(call, monkeypatch, caplog):
    """With the platform probe answering "TPU" on this CPU (ONE name: every dispatcher
    asks `tiers.on_tpu` through the module), the real kernel is asked for a Mosaic
    lowering and refuses. The dispatcher hands that on: it used to warn once and run
    the reference, which would hide a kernel the chip's compiler rejects."""
    monkeypatch.setattr("modalities_tpu.ops.tiers.on_tpu", lambda: True)
    with pytest.raises(ValueError, match="Only interpret mode is supported on CPU backend"):
        call()
    assert not [r for r in caplog.records if "unavailable" in r.getMessage()]


@pytest.mark.parametrize(
    "probe",
    [
        "modalities_tpu.ops.tiers:on_tpu",
        "modalities_tpu.ops.tiers:kernels_run",
        "modalities_tpu.ops.pallas.autotune:device_kind_slug",
        "modalities_tpu.utils.mfu:get_peak_flops",
    ],
)
def test_platform_probe_raises_when_the_backend_does(probe, monkeypatch):
    """A backend that fails to initialise is an error, never "not a TPU"."""
    import importlib

    import jax

    def no_backend(*args, **kwargs):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    module_name, function = probe.split(":")
    module = importlib.import_module(module_name)
    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        getattr(module, function)()
