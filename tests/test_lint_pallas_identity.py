"""Lint: every pallas kernel module carries an interpret-mode reference
test AND a compile-for-the-chip test.

Sibling of the ``test_lint_*`` family. The repo's kernel contract
(``docs/serving.md``, restated in PR 22 after the first chip run) is
that every hand-tiled pallas kernel in ``models/pallas_*.py``

- equals its XLA reference path up to f32 accumulation order under
  interpret mode on the CPU tier (a few ulps — the bitwise form could
  not be lowered by the chip's compiler and is gone), which is what the
  serve suites' enforced 0-mismatch token pins rest on, and
- is accepted by the chip's compiler at the shapes the engine passes it
  (or is a declared ``xfail(strict=True)`` there): a kernel that has
  only ever run interpreted says nothing about the chip.

A kernel module that ships without either silently downgrades the
contract, so this lint makes the pairing structural: for every
``ray_lightning_tpu/models/pallas_<name>.py`` there must be a
``tests/test_pallas_<name>.py`` that

- imports the kernel module (references ``pallas_<name>``),
- runs it under **interpret mode** (mentions ``interpret``), and
- compares it numerically against a reference (``assert_allclose`` or
  ``array_equal``),

and ``tests/test_chip_compile.py`` must compile it (reference
``pallas_<name>`` and assert ``tpu_custom_call``).

``pallas_attention`` and ``pallas_matmul`` both satisfy it today; a
future kernel module fails this lint until both tests land.
"""
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
KERNELS = sorted(
    (ROOT / "ray_lightning_tpu" / "models").glob("pallas_*.py"))


def test_kernel_modules_discovered():
    names = [p.stem for p in KERNELS]
    assert "pallas_attention" in names and "pallas_matmul" in names


@pytest.mark.parametrize("module", KERNELS, ids=lambda p: p.stem)
def test_every_pallas_kernel_has_reference_and_compile_tests(module):
    test_path = ROOT / "tests" / f"test_{module.stem}.py"
    assert test_path.exists(), (
        f"kernel module models/{module.stem}.py has no "
        f"tests/test_{module.stem}.py — every pallas kernel needs an "
        "interpret-mode reference test (what the serve suites' enforced "
        "0-mismatch token pins rest on; docs/serving.md)")
    src = test_path.read_text()
    assert re.search(rf"\b{module.stem}\b", src), (
        f"tests/test_{module.stem}.py never references {module.stem}")
    assert "interpret" in src, (
        f"tests/test_{module.stem}.py has no interpret-mode coverage — "
        "the CPU tier's identity contract runs the kernel under "
        "pallas interpret mode")
    assert re.search(r"\b(assert_allclose|array_equal)\b", src), (
        f"tests/test_{module.stem}.py never compares the kernel "
        "numerically against a reference (assert_allclose/array_equal)")
    chip = (ROOT / "tests" / "test_chip_compile.py").read_text()
    assert re.search(rf"\b{module.stem}\b", chip) \
        and "tpu_custom_call" in chip, (
        f"tests/test_chip_compile.py never compiles {module.stem} for "
        "the described chip — interpret mode says nothing about what "
        "the chip's compiler accepts")
