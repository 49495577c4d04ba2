"""Lint: every span name and every device scope of the package has a row
in ``docs/observability.md``.

The span/scope half of the ``test_lint_obs_docs.py`` contract. A span in
a profile and an ``op_name`` in a device trace are read by name — by
``tools/trace_report.py --profile``, by the benchmark's per-layer metric
files — so a name nobody documented is a name nobody can read. The lint
walks the library AST and collects:

- span names: string literals passed first to ``*.span(...)`` /
  ``*._span(...)`` / ``*.begin(...)``, and third to the trainer's
  ``_spanned(tel, iterable, name)``;
- device scopes: string literals passed to ``jax.named_scope(...)``
  (as a context manager or a decorator) and the ``name=`` keyword of a
  ``pallas_call``;

and asserts each appears back-quoted in the doc's "Spans" / "Device
scopes" tables. The doc's clock section must state the three clock
modes and ``last_telemetry()``.
"""
import ast
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
PKG = ROOT / "ray_lightning_tpu"
DOC = ROOT / "docs" / "observability.md"

SPAN_ATTRS = {"span", "_span", "begin"}


def _literal(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _callee(node):
    f = node.func
    return f.attr if isinstance(f, ast.Attribute) else getattr(f, "id", "")


def _collect():
    spans, scopes = {}, {}
    for path in sorted(PKG.rglob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        rel = path.relative_to(ROOT)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            site = f"{rel}:{node.lineno}"
            callee = _callee(node)
            if callee in SPAN_ATTRS and node.args:
                name = _literal(node.args[0])
                if name is not None and "." in name:
                    spans.setdefault(name, site)
            elif callee == "_spanned" and len(node.args) >= 3:
                name = _literal(node.args[2])
                if name is not None:
                    spans.setdefault(name, site)
            elif callee == "named_scope" and node.args:
                name = _literal(node.args[0])
                if name is not None:
                    scopes.setdefault(name, site)
            elif callee == "pallas_call":
                for kw in node.keywords:
                    if kw.arg == "name" and _literal(kw.value):
                        scopes.setdefault(_literal(kw.value), site)
    return spans, scopes


SPANS, SCOPES = _collect()


def test_span_and_scope_names_discovered():
    # sanity: the walker sees every emission shape (a refactor that
    # changes them must update this lint, not silently stop collecting)
    assert "serve.tick" in SPANS              # client _span helper
    assert "engine.step.sync" in SPANS        # tel.span(...) in the engine
    assert "trainer.train_step" in SPANS      # trainer _span helper
    assert "trainer.get_train_batch" in SPANS  # the _spanned iterator
    assert "attention/scores" in SCOPES       # context manager
    assert "kv_commit" in SCOPES              # decorator
    assert "paged_attention" in SCOPES        # pallas_call name=
    assert "flash_attention_fwd" in SCOPES
    assert len(SPANS) >= 20 and len(SCOPES) >= 25


@pytest.mark.parametrize("name", sorted(SPANS), ids=str)
def test_every_span_name_is_documented(name):
    assert f"`{name}`" in DOC.read_text(), (
        f"span {name!r} (opened at {SPANS[name]}) has no row in the "
        "\"Spans\" table of docs/observability.md: name, where, args, "
        "which metric reads it")


@pytest.mark.parametrize("name", sorted(SCOPES), ids=str)
def test_every_device_scope_is_documented(name):
    assert f"`{name}`" in DOC.read_text(), (
        f"device scope {name!r} (at {SCOPES[name]}) has no row in the "
        "\"Device scopes\" table of docs/observability.md")


@pytest.mark.parametrize("phrase", [
    "**tick mode**", "**wall mode**", "**inside a profile**",
    "`obs.last_telemetry()`", "**Spans**", "**Device scopes**"])
def test_docs_state_the_clock_modes_and_the_tables(phrase):
    assert phrase in DOC.read_text()
