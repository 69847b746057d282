"""Where the collectives of a compiled HLO text lie: helper of the tests
that assert a step's communication structure (no test of its own)."""
from __future__ import annotations

import re

import numpy as np

_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{\s*$")
_CALLEE = re.compile(
    r"(?:to_apply|calls|body|condition|branch_computations)=\{?%?([\w.\-]+)")
_COLLECTIVE = re.compile(
    r"%?([\w.\-]+) = (\(?[^=]*?\)?) "
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


_GROUPS = re.compile(
    r"replica_groups=(?:\{(\{[\d,{}]*\})\}"
    r"|\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?)")


def replica_groups(line):
    """The groups of a collective's line as a frozenset of frozensets of
    device ids, from either form the compiler writes (`{{0,2},{1,3}}`,
    `[2,2]<=[2,2]T(1,0)`); None where the line gives none."""
    m = _GROUPS.search(line)
    if not m:
        return None
    if m.group(1) is not None:
        return frozenset(
            frozenset(int(i) for i in g.split(",") if i)
            for g in re.findall(r"\{([\d,]*)\}", m.group(1)))
    ids = np.arange(int(m.group(2)) * int(m.group(3))).reshape(
        [int(d) for d in m.group(4).split(",")])
    if m.group(5):
        ids = ids.transpose([int(d) for d in m.group(5).split(",")])
    return frozenset(frozenset(int(i) for i in row) for row in ids.reshape(
        int(m.group(2)), int(m.group(3))))


def _computations(text):
    comps, cur = {}, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def collectives(text):
    """[(opcode, result shapes without layouts, computation, while_body,
    replica groups)] of every collective in `text`; `while_body` names the
    loop body the computation is (or is called from, fusions and reducers
    included), or is None outside every loop; the groups as
    `replica_groups` gives them."""
    comps = _computations(text)
    inside = {}
    for lines in comps.values():
        for line in lines:
            m = re.search(r"\bwhile\(.*body=%?([\w.\-]+)", line)
            if not m:
                continue
            stack = [m.group(1)]
            while stack:
                c = stack.pop()
                if c in inside:
                    continue
                inside[c] = m.group(1)
                for callee_line in comps.get(c, ()):
                    stack.extend(_CALLEE.findall(callee_line))
    out = []
    for name, lines in comps.items():
        for line in lines:
            m = _COLLECTIVE.search(line)
            if m:
                shape = re.sub(r"\{[^}]*\}", "", m.group(2))
                out.append((m.group(3), shape, name, inside.get(name),
                            replica_groups(line)))
    return out


def while_bodies(text):
    """Names of the computations that are loop bodies."""
    return set(re.findall(r"\bwhile\(.*body=%?([\w.\-]+)", text))
