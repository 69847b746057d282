"""Device operations under the Fluid op that made them.

``framework.trace.trace_op`` traces every Fluid op's kernel under
``jax.named_scope("fl.<op.type>:<anchor>")`` (the anchor: the op's
first persistable input, ``lm.l3.ffn.w1``, else its first output), and
the special mechanisms inside ``paddle_tpu/ops`` open ``ptpu.*`` scopes
of their own, nested inside it. A scope lives in an instruction's
``op_name``, which an executable's text carries for every instruction,
those inside a fused computation too, through ``serialize`` and
``deserialize_and_load``. A profiler's device event carries only the
instruction's NAME (``fusion.524``) and its module's
(``jit_ptpu_prefill_b1_s512``): ``scope_map`` is the table from the one
to the other, and to the weights each instruction reads.

``Engine.acquire`` registers every executable it returns (loaded or
compiled) under its record's ``name``: one dict insert, no text. A
reader that wants the tables asks ``maps()`` after the run, which
renders each executable's text once and parses it; nothing here depends
on the trace sample rate. Beside a profiler session::

    jax.profiler.start_trace(d); serve(); jax.profiler.stop_trace()
    for name, m in scopes.maps().items():      # m["module"] is the
        m["ops"]["fusion.187"]                 # `XLA Modules` event's name
    # {"scope": ["fl.mul:lm.l7.ffn.fc2.w"], "pass": "fwd",
    #  "members": ["fl.mul:lm.l7.ffn.fc2.w", "fl.gelu:gelu_7.tmp_0"],
    #  "users": [], "copied": ["state['lm.l7.ffn.fc2.b']"],
    #  "reads": ["state['lm.l7.ffn.fc2.w']", "state['lm.l7.ffn.fc2.b']"]}
"""
from __future__ import annotations

import collections
import re
import threading
import warnings
from typing import Dict, Iterable, List, Optional

__all__ = ["scope_map", "scope_path", "register", "maps", "reset"]

# the executables of the newest acquisitions, by record name; the oldest
# goes when a process has acquired more than this many distinct names
_KEEP = 256
MEMBERS_MAX = 8
# how far an unscoped instruction's result is followed to a scoped user:
# slice-start -> slice-done -> the join of the slices -> the product
_USERS_DEPTH = 6

_SCOPE = re.compile(r"(?<![\w.])(?:fl|ptpu)\.[^/()\"]+")
_HEADER = re.compile(r"^(ENTRY )?%([^\s(]+) \(.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%([^\s=]+) = (.*)$")
_OPCODE = re.compile(r"(?:^| )([a-z][a-z0-9\-]*)\(")
_OPERANDS_END = re.compile(r"\)(?:, [a-z_]+=|\s*$)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(
    r"\b(calls|to_apply|condition|body|true_computation|false_computation)"
    r"=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_SHAPE = re.compile(r"^([a-z]+\d*[a-z0-9]*)\[([\d,]*)\]")
_BITS = re.compile(r"(\d+)")
_CONCAT_BITCAST = 'custom_call_target="ConcatBitcast"'

# what hands a value on without computing: a read of an entry parameter
# is followed through these to the instruction that does the work
_PASS_THROUGH = frozenset((
    "bitcast", "copy", "convert", "get-tuple-element", "copy-start",
    "copy-done", "slice-start", "slice-done", "optimization-barrier",
    "concat-bitcast"))
# those of them that move no byte and leave no event: what reaches an
# instruction through these alone it reads where the parameter lies, in
# HBM. Any other is an operation of its own that reads the parameter
# (the halves of a prefetch, which runs beside the operations after it;
# the TPU compiler's `copy`, which may round a float32 weight to
# bfloat16 and leave it in on-chip memory, `S(1)`): ITS reader reads
# another array, of other bytes, maybe not from HBM at all
_IN_PLACE = frozenset(("bitcast", "get-tuple-element",
                       "optimization-barrier"))
# what leaves no event of its own on a device's operation line
_NO_EVENT = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast"))


def scope_path(op_name: str) -> List[str]:
    """The ``fl.`` and ``ptpu.`` components of an ``op_name``, outermost
    first: ``jit(f)/jvp(fl.mul:w)/ptpu.inner/dot`` -> ``["fl.mul:w",
    "ptpu.inner"]``."""
    return _SCOPE.findall(op_name)


def _nbytes(type_text: str) -> int:
    """Bytes of ``f32[4096,4096]{1,0:T(8,128)}``; 0 for a tuple or a
    type this cannot size."""
    m = _SHAPE.match(type_text)
    if not m:
        return 0
    bits = _BITS.search(m.group(1))
    width = 8 if m.group(1) == "pred" else int(bits.group(1)) if bits else 0
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n * width // 8


class _Instr:
    __slots__ = ("name", "comp", "opcode", "operands", "op_name", "called",
                 "index", "type_text")

    def __init__(self, name, comp, rest):
        self.name, self.comp = name, comp
        m = _OPCODE.search(rest)
        self.type_text = rest[:m.start(1)] if m else rest
        self.opcode = m.group(1) if m else ""
        tail = rest[m.end():] if m else ""
        end = _OPERANDS_END.search(tail)
        inside = tail[:end.start()] if end else tail
        attrs = tail[end.start():] if end else ""
        if self.opcode == "custom-call" and _CONCAT_BITCAST in attrs:
            # the TPU compiler's join of a weight's prefetched slices
            self.opcode = "concat-bitcast"
        self.operands = ([] if self.opcode in ("constant", "parameter")
                         else _OPERAND.findall(inside))
        self.index = None
        if self.opcode == "parameter":
            self.index = int(inside) if inside.isdigit() else 0
        elif self.opcode == "get-tuple-element":
            found = re.search(r"\bindex=(\d+)", attrs)
            self.index = int(found.group(1)) if found else 0
        found = _OP_NAME.search(attrs)
        self.op_name = found.group(1).replace("\\'", "'") if found else ""
        self.called = [c for _, c in _CALLED.findall(attrs)]
        found = _BRANCHES.search(attrs)
        if found:
            self.called += _OPERAND.findall(found.group(1))


def _parse(text: str):
    """-> (module name, entry computation, {computation: [instructions]},
    {instruction name: instruction})."""
    module = re.match(r"HloModule ([^\s,]+)", text)
    comps, instrs, entry, comp = {}, {}, None, None
    for line in text.splitlines():
        if not line:
            continue
        if line[0] == " ":
            if comp is None:
                continue
            m = _INSTR.match(line)
            if m:
                ins = _Instr(m.group(1), comp, m.group(2))
                comps[comp].append(ins)
                instrs[ins.name] = ins
        elif line[0] == "}":
            comp = None
        else:
            m = _HEADER.match(line)
            if m:
                comp = m.group(2)
                comps[comp] = []
                if m.group(1):
                    entry = comp
    return (module.group(1) if module else ""), entry, comps, instrs


def scope_map(compiled) -> Dict:
    """The table of one executable, from ONE ``as_text()`` (module doc)::

        {"module": <HloModule name>, "scoped": <any fl. scope in it>,
         "ops": {instruction: {"scope": [its own op_name's fl./ptpu.
                     components, outermost first],
                 "pass": "fwd" | "bwd" (under ``transpose(..)``),
                 "members": [distinct fl./ptpu. leaves of the instructions
                     inside its called computation, most instructions
                     first, at most 8],
                 "users": [for an instruction with neither (a weight's
                     prefetch, the compiler's expansion of a ragged dot):
                     the leaves of the scoped instructions its result
                     goes to, nearest first, at most 8; else empty],
                 "reads": [entry parameters it takes, by name, followed
                     through bitcasts, copies, converts, tuple elements
                     and into a loop's body],
                 "copied": [those of them that reach it ONLY through
                     an operation that moves them (``copy-start`` ..
                     ``copy-done``, a weight's slices, a ``copy`` into
                     on-chip memory, a conversion): that operation reads
                     the parameter's bytes, beside other operations or in
                     an event of its own; this one reads what it left,
                     another array, not the parameter in HBM]}},
         "params": {entry parameter's name: bytes}}

    ``ops`` holds every instruction that can leave an event: the entry
    computation's and those of the bodies, conditions and branches it
    runs, not the inside of a fusion. A parameter's name is its
    argument's path (``state['lm.l3.ffn.w1']``). ``"scoped": false``
    says the text carries no ``fl.`` scope at all (a blob stored before
    the tracer opened them): its operations are not unnamed work, they
    are unread. ``compiled`` is anything with ``as_text()``, or the text.
    """
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    module, entry, comps, instrs = _parse(text)
    # the computations whose instructions run as operations of their own
    running, caller, todo = set(), {}, [entry] if entry else []
    while todo:
        comp = todo.pop()
        if comp in running or comp not in comps:
            continue
        running.add(comp)
        for ins in comps[comp]:
            if ins.opcode in ("while", "conditional", "call"):
                for c in ins.called:
                    caller.setdefault(c, ins)
                    todo.append(c)

    params = {}
    for ins in comps.get(entry, ()):
        if ins.opcode == "parameter":
            params[ins.op_name or ins.name] = _nbytes(ins.type_text)

    memo: Dict = {}
    none = frozenset()

    def element(name: str, index: int, seen) -> frozenset:
        """`sources` of element ``index`` of a tuple value."""
        ins = instrs.get(name)
        if ins is None:
            return none
        if ins.opcode == "tuple":
            return (sources(ins.operands[index], seen)
                    if index < len(ins.operands) else none)
        if ins.opcode == "while":  # a weight rides a loop unchanged
            return element(ins.operands[0], index, seen) if ins.operands \
                else none
        if ins.opcode == "parameter" and ins.comp in caller:
            via = caller[ins.comp]
            if via.opcode == "while" and via.operands:
                return element(via.operands[0], index, seen)
        return sources(name, seen) if ins.opcode in _PASS_THROUGH else none

    def sources(name: str, seen=none) -> frozenset:
        """{(entry parameter, whether an operation moved it on the
        way)} an instruction's value IS, unchanged."""
        if name in memo:
            return memo[name]
        ins = instrs.get(name)
        if ins is None or name in seen:
            return none
        seen = seen | {name}
        out = none
        if ins.opcode == "parameter":
            if ins.comp == entry:
                out = frozenset(((ins.op_name or ins.name, False),))
            elif ins.comp in caller:
                via = caller[ins.comp]
                if via.opcode == "call" and ins.index < len(via.operands):
                    out = sources(via.operands[ins.index], seen)
                elif (via.opcode == "conditional"
                      and ins.comp in via.called):
                    k = via.called.index(ins.comp) + 1
                    if k < len(via.operands):
                        out = sources(via.operands[k], seen)
        elif ins.opcode == "get-tuple-element" and ins.operands:
            out = element(ins.operands[0], ins.index, seen)
        elif ins.opcode in _PASS_THROUGH:
            for o in ins.operands:
                out |= sources(o, seen)
            if ins.opcode not in _IN_PLACE:
                out = frozenset((p, True) for p, _ in out)
        memo[name] = out
        return out

    def members(comp: str, counts, seen):
        if comp in seen:
            return
        seen.add(comp)
        for ins in comps.get(comp, ()):
            path = scope_path(ins.op_name)
            if path:
                key = (path[-1], "transpose(" in ins.op_name)
                counts[key] = counts.get(key, 0) + 1
            for c in ins.called:
                members(c, counts, seen)

    ops, users = {}, {}
    for comp in running:
        for ins in comps[comp]:
            for o in ins.operands:
                users.setdefault(o, []).append(ins)
            if ins.opcode in _NO_EVENT:
                continue
            counts: Dict = {}
            if ins.opcode not in ("while", "conditional", "call"):
                for c in ins.called:
                    members(c, counts, set())
            ranked = sorted(counts, key=lambda k: -counts[k])
            leaves = list(dict.fromkeys(k[0] for k in ranked))
            path = scope_path(ins.op_name)
            bwd = ("transpose(" in ins.op_name if path
                   else bool(ranked) and ranked[0][1])
            reads = none
            for o in ins.operands:
                reads |= sources(o)
            direct = {p for p, copied in reads if not copied}
            ops[ins.name] = {"scope": path, "pass": "bwd" if bwd else "fwd",
                             "members": leaves[:MEMBERS_MAX], "users": [],
                             "reads": sorted({p for p, _ in reads}),
                             "copied": sorted(
                                 {p for p, _ in reads} - direct)}

    def leaf_and_pass(ins):
        own = ops.get(ins.name)
        if own and own["scope"]:
            return own["scope"][-1], own["pass"]
        if own and own["members"]:
            return own["members"][0], own["pass"]
        path = scope_path(ins.op_name)  # a bitcast, a tuple element
        return (path[-1] if path else None,
                "bwd" if "transpose(" in ins.op_name else "fwd")

    # an instruction the compiler made with no scope (a weight's
    # prefetch, the expansion of a ragged dot): whom its result goes to
    for name, entry in ops.items():
        if entry["scope"] or entry["members"]:
            continue
        found, seen, frontier = {}, {name}, [name]
        for _ in range(_USERS_DEPTH):
            step = []
            for n in frontier:
                for u in users.get(n, ()):
                    if u.name in seen:
                        continue
                    seen.add(u.name)
                    leaf, which = leaf_and_pass(u)
                    if leaf is None:
                        step.append(u.name)
                    else:
                        found.setdefault(leaf, which)
            if found or not step:
                break
            frontier = step
        if found:
            entry["users"] = list(found)[:MEMBERS_MAX]
            entry["pass"] = found[entry["users"][0]]
    scoped = any(s.startswith("fl.") for o in ops.values()
                 for s in o["scope"] + o["members"])
    return {"module": module, "scoped": scoped, "ops": ops, "params": params}


# -- the registry -------------------------------------------------------------

_LOCK = threading.Lock()
# record name -> [executable, its map once rendered]
_EXECUTABLES: "collections.OrderedDict[str, list]" = collections.OrderedDict()


def register(name: str, compiled) -> None:
    """Remember the executable an acquisition returned, under its
    record's name. Holds no text and renders nothing."""
    with _LOCK:
        _EXECUTABLES[name] = [compiled, None]
        _EXECUTABLES.move_to_end(name)
        while len(_EXECUTABLES) > _KEEP:
            _EXECUTABLES.popitem(last=False)


def maps(names: Optional[Iterable[str]] = None) -> Dict[str, Dict]:
    """``{record name: scope_map}`` of the registered executables (of
    ``names`` where given), each rendered and parsed at its first asking
    and kept. An executable whose text cannot be had is left out."""
    names = None if names is None else set(names)
    with _LOCK:
        wanted = [(n, e) for n, e in _EXECUTABLES.items()
                  if names is None or n in names]
    out = {}
    for name, entry in wanted:
        if entry[1] is None:
            try:
                entry[1] = scope_map(entry[0])
            except Exception as e:  # a reader's table never breaks a run
                warnings.warn("scopes.maps: no map of %s: %r" % (name, e))
                continue
        out[name] = entry[1]
    return out


def reset() -> None:
    with _LOCK:
        _EXECUTABLES.clear()
