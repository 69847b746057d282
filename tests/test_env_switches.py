"""The census of `PADDLE_TPU_*` environment names: every name a `grep` of
`paddle_tpu/` finds stands in the ONE table of `docs/internals.md`
("Environment names") with its class, and is read by code and not only
named by a comment; the table holds no name the package does not read;
and the AOT cache keys on exactly the table's compile levers. A new
switch is a failing test here until someone writes down who sets it."""
import ast
import functools
import os
import re

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NAME = re.compile(r"PADDLE_TPU_[A-Z0-9_]+")
CLASSES = ("deployment", "fault injection", "debug", "compile lever")


def _sources():
    for d, _, files in os.walk(os.path.join(_ROOT, "paddle_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    yield fh.read()


def _grep():
    """What `grep -rhoE 'PADDLE_TPU_[A-Z0-9_]+' paddle_tpu | sort -u`
    prints (the sources; a .pyc holds the same names)."""
    return sorted({n for text in _sources() for n in _NAME.findall(text)})


@functools.lru_cache(None)
def _in_code():
    """The names inside string constants that are not docstrings."""
    found = set()
    for text in _sources():
        tree = ast.parse(text)
        docs = set()
        for node in ast.walk(tree):
            if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                body = node.body
                if (body and isinstance(body[0], ast.Expr)
                        and isinstance(body[0].value, ast.Constant)):
                    docs.add(id(body[0].value))
        for node in ast.walk(tree):
            if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                    and id(node) not in docs):
                found.update(_NAME.findall(node.value))
    return found


@functools.lru_cache(None)
def _table():
    """{name: class} of the table's rows, and how many tables hold one."""
    with open(os.path.join(_ROOT, "docs", "internals.md")) as f:
        lines = f.read().splitlines()
    rows, tables, inside = {}, 0, False
    for line in lines:
        m = re.match(r"\| `(PADDLE_TPU_[A-Z0-9_]+)` \| ([a-z ]+) \|", line)
        if m:
            assert m.group(1) not in rows, "twice: " + m.group(1)
            rows[m.group(1)] = m.group(2)
            tables += not inside
        inside = bool(m)
    return rows, tables


NAMES = _grep()


@pytest.mark.parametrize("name", NAMES)
def test_name_is_in_the_table_and_read_by_code(name):
    rows, tables = _table()
    assert tables == 1, "the names live in ONE table of docs/internals.md"
    assert rows.get(name) in CLASSES, (
        "%s: no row of docs/internals.md's 'Environment names' table "
        "gives it one of the classes %s" % (name, CLASSES))
    code = _in_code()
    # a name that ends in `_` is a family: code reads its members
    read = (any(c.startswith(name) for c in code) if name.endswith("_")
            else name in code)
    assert read, "%s is named by comments only: nothing reads it" % name


def test_the_table_holds_no_name_the_package_does_not_read():
    rows, _ = _table()
    assert sorted(rows) == NAMES


def test_the_aot_key_holds_the_compile_levers():
    from paddle_tpu.runtime import aot_cache

    rows, _ = _table()
    assert sorted(aot_cache._TRACE_ENV) == sorted(
        n for n, c in rows.items() if c == "compile lever")
