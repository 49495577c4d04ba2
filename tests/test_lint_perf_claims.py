"""Text lint: nothing a reader meets cites the retired measuring harness.

Sibling of the other ``test_lint_*`` files. PR 30 deleted the repo's old
yardstick — the root-level bench script, its ``extras[...]`` legs, the
reference / per-round / multichip record files it fed and the sweep
tool that imported it. A speed is produced by ``BENCHMARK.json`` +
``benchmark/``, recorded in ``PERF_LEDGER.jsonl`` and explained in
``PERF.md``; a doc or a docstring that needs a number cites a ``PERF.md``
section and cell, or says "not measured on the chip". This lint keeps
the next doc from quoting a harness that no longer exists.

In scope: ``README.md``, every ``docs/*.md``, and the ``.py`` files of
the package, ``examples/`` and ``tools/``. Out of scope: ``CHANGES.md``,
``ROADMAP.md`` and ``PERF.md`` (they record the retirement by name) and
``benchmark/`` (only a ``benchmark`` PR may edit it).
"""
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

# the record files' and the sweep tool's names are spelled in halves so
# that this file does not itself answer a grep for them
RETIRED = re.compile("|".join([
    r"\bbench\.py", r'extras\["', "BENCH_" + "REFERENCE", "BENCH_" + "r0",
    "MULTICHIP_" + "r0", "ab_" + "sweep"]))

#: one case per doc, one per tree of ``.py`` files
TARGETS = {str(p.relative_to(REPO)): [p] for p in
           [REPO / "README.md"] + sorted((REPO / "docs").glob("*.md"))}
TARGETS.update({tree: sorted((REPO / tree).rglob("*.py"))
                for tree in ("ray_lightning_tpu", "examples", "tools")})


def _hits(path):
    return [f"{path.relative_to(REPO)}:{n}: {m.group(0)}"
            for n, line in enumerate(path.read_text().splitlines(), 1)
            for m in RETIRED.finditer(line)]


@pytest.mark.parametrize("target", TARGETS)
def test_no_text_cites_the_retired_harness(target):
    assert TARGETS[target], target
    hits = [h for p in TARGETS[target] for h in _hits(p)]
    assert not hits, (
        "cites the measuring harness PR 30 retired — keep the mechanism "
        "and the count, drop the rate, and for a number cite a PERF.md "
        "section and cell or say 'not measured on the chip': "
        + "; ".join(hits))
