"""`ops/tiers.py`: the one rule that says which form of an operation runs, and the one seam through which tests reach a
kernel off the chip."""

import ast
from pathlib import Path

import pytest

from modalities_tpu.ops import tiers

PACKAGE = Path(tiers.__file__).parents[1]


def test_off_the_chip_the_reference_runs_and_inside_the_seam_the_kernels_interpreted():
    assert not tiers.on_tpu() and not tiers.kernels_run()
    assert tiers.interpret() and tiers.interpret(True)  # a call that got as far as a kernel here can only be interpreted
    with tiers.interpreted_kernels():
        assert tiers.kernels_run() and tiers.interpret()
        with tiers.interpreted_kernels():  # nests, and the inner one leaves the outer as it was
            assert tiers.kernels_run()
        assert tiers.kernels_run()
    assert not tiers.kernels_run()
    with pytest.raises(RuntimeError, match="inside"):
        with tiers.interpreted_kernels():
            raise RuntimeError("inside")
    assert not tiers.kernels_run()  # closed behind a test that failed, too


def test_on_a_tpu_the_kernels_run_compiled_unless_a_caller_asks(monkeypatch):
    """One name to patch: the rule's other questions ask `on_tpu` through the module."""
    monkeypatch.setattr(tiers, "on_tpu", lambda: True)
    assert tiers.kernels_run() and not tiers.interpret() and tiers.interpret(True)
    with tiers.interpreted_kernels():
        assert tiers.kernels_run() and not tiers.interpret()  # the seam adds nothing where the kernels run anyway


def test_nothing_but_python_reaches_the_seam_and_nothing_overrides_the_rule():
    """`tiers.py` reads no environment, and the package enters the seam nowhere: no variable, config key or CLI flag can."""
    source = (PACKAGE / "ops" / "tiers.py").read_text()
    names = {node.attr if isinstance(node, ast.Attribute) else getattr(node, "id", None) for node in ast.walk(ast.parse(source))}
    assert not names & {"environ", "getenv"} and "import os" not in source
    entered = [str(path.relative_to(PACKAGE)) for path in sorted(PACKAGE.rglob("*.py"))
               if path.name != "tiers.py" and "interpreted_kernels" in path.read_text()]
    assert not entered, entered


def test_every_dispatcher_asks_the_rule_through_the_module():
    """No wrapper binds its own copy of `on_tpu` (a test would have to patch each), and `interpret` is worked out by
    `tiers.interpret` alone: nowhere else does the package write `not on_tpu()`."""
    offenders = []
    for path in sorted(PACKAGE.rglob("*.py")):
        text = path.read_text()
        if path.name != "tiers.py" and ("import on_tpu" in text or "not on_tpu()" in text or "not tiers.on_tpu()" in text):
            offenders.append(str(path.relative_to(PACKAGE)))
    assert not offenders, offenders
