"""Collective census: which exchanges a compiled program holds, read from
its text.

The counter that says whether ``parallel.sharding.constrain_batch``
engages: under a strategy whose parameters are cut over ``fsdp`` the
compiled train step should hold **parameter-shaped** collectives only
(all-gathers of a layer's weights inside the layer scan's ``while`` body,
the gradients' reduction) and none that **carries the global batch dim**
(partial activations or attention scores summed across chips — the
partitioner computing on the stored cut). ``tests/test_fsdp_schedule.py``
holds the step to that; ``tools/chip_profile.py --census`` prints it for a
benchmark cell's step on the chip.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import re
from typing import Any, Dict, Iterator, List, Optional, Set, Tuple

KINDS = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
         "collective-permute")

_OP = re.compile(
    r"=\s*(?P<type>\(.*?\)|\S+)\s+(?P<kind>" + "|".join(KINDS) +
    r")(?P<start>-start)?\(")
_ARRAY = re.compile(r"([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLEE = re.compile(
    r"(?:body|condition|to_apply|calls|true_computation|false_computation"
    r"|branch_computations)=(?:\{([^}]*)\}|(%?[\w.\-]+))")


@dataclasses.dataclass(frozen=True)
class Collective:
    kind: str                   # one of KINDS
    dtype: str
    shape: Tuple[int, ...]      # the result's (one entry per result array)
    in_loop: bool               # inside a ``while`` body (a scanned layer)
    carries_batch: bool         # leading dim is the global batch

    @property
    def bytes(self) -> int:
        # an HLO dtype names its width (bf16, f32, f8e4m3fn); pred has none
        bits = re.search(r"\d+", self.dtype)
        return math.prod(self.shape) * max(1, int(bits.group()) // 8
                                           if bits else 1)


def _lines_by_computation(text: str) -> Iterator[Tuple[str, str]]:
    """``(computation name, instruction line)`` for every instruction."""
    current = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head and not line.startswith(" "):
            current = head.group(1)
        else:
            yield current, line


def _loop_computations(text: str) -> Set[str]:
    """Names of the computations a ``while`` body reaches."""
    calls: Dict[str, Set[str]] = collections.defaultdict(set)
    todo: List[str] = []
    for current, line in _lines_by_computation(text):
        for m in _CALLEE.finditer(line):
            names = [n.strip().lstrip("%")
                     for n in (m.group(1) or m.group(2)).split(",")]
            calls[current].update(names)
            if m.group(0).startswith("body="):
                todo.extend(names)
    seen: Set[str] = set()
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            todo.extend(calls.get(name, ()))
    return seen


def collective_census(compiled: Any,
                      batch: Optional[int] = None) -> List[Collective]:
    """Every collective of ``compiled`` (a ``jax.stages.Compiled``, or its
    text), one entry per result array. ``batch`` is the global batch size:
    a result whose leading dim equals it ``carries_batch``."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    in_loop = _loop_computations(text)
    out: List[Collective] = []
    for current, line in _lines_by_computation(text):
        m = _OP.search(line)
        if m is None:
            continue
        arrays = _ARRAY.findall(m.group("type"))
        if m.group("start") and m.group("kind") != "all-reduce":
            # an async start's type is (operands, results, contexts): the
            # contexts are scalars, the results the arrays' second half
            arrays = [a for a in arrays if a[1]]
            arrays = arrays[len(arrays) // 2:]
        for dtype, dims in arrays:
            shape = tuple(int(d) for d in dims.split(",") if d)
            out.append(Collective(
                kind=m.group("kind"), dtype=dtype, shape=shape,
                in_loop=current in in_loop,
                carries_batch=bool(shape) and shape[0] == batch))
    return out


def format_census(census: List[Collective]) -> str:
    """One line per (kind, result type, where): count and bytes, the
    batch-carrying ones first."""
    groups = collections.Counter(
        (not c.carries_batch, c.kind,
         f"{c.dtype}[{','.join(map(str, c.shape))}]", c.in_loop, c.bytes)
        for c in census)
    lines = []
    for (param, kind, typ, loop, size), n in sorted(groups.items()):
        lines.append(
            f"{'parameter-shaped' if param else 'carries the batch':17s} "
            f"{kind:18s} {typ:28s} x{n:<3d} {size * n / 1e6:10.3f} MB  "
            f"{'in the layer loop' if loop else 'outside the loop'}")
    return "\n".join(lines)
