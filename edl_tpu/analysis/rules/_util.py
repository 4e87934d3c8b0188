"""Shared AST helpers for the project rules."""

from __future__ import annotations

import ast
from typing import Iterator, Optional, Sequence, Set, Tuple

JIT_NAMES = {"jax.jit", "jit", "pjit", "jax.pjit"}
PARTIAL_NAMES = {"partial", "functools.partial"}


def dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class Argnums(tuple):
    """Argument positions of a jitted callee: the literal ones, as a
    tuple, and ``tail`` — where a run of computed length starts
    (``tuple(range(6, 6 + n))``: 6; None when there is none). A program
    that takes a variable-length group of arrays (``*cache``) donates
    such a run; how far it reaches is only known where it is called."""

    tail: Optional[int]

    def __new__(cls, fixed=(), tail: Optional[int] = None):
        self = super().__new__(cls, fixed)
        self.tail = tail
        return self

    def __bool__(self) -> bool:
        return len(self) > 0 or self.tail is not None

    def __or__(self, other: "Argnums") -> "Argnums":
        tails = [t for t in (self.tail, other.tail) if t is not None]
        return Argnums(sorted(set(self) | set(other)),
                       min(tails) if tails else None)


def _int_range(call: ast.Call) -> Optional[Argnums]:
    """``range(b)`` / ``range(a, b)`` with a literal start: the whole
    run when ``b`` is literal too; when ``b`` is a literal plus a name
    (or a name alone), the literal part and an open tail behind it."""
    args = call.args
    if not 1 <= len(args) <= 2 or call.keywords:
        return None
    lo = 0
    if len(args) == 2:
        if not (isinstance(args[0], ast.Constant)
                and isinstance(args[0].value, int)):
            return None
        lo = args[0].value
    hi = args[-1]
    if isinstance(hi, ast.Constant) and isinstance(hi.value, int):
        return Argnums(range(lo, hi.value))
    if isinstance(hi, ast.Name):
        return Argnums((), tail=lo)
    if (
        isinstance(hi, ast.BinOp) and isinstance(hi.op, ast.Add)
        and isinstance(hi.left, ast.Constant)
        and isinstance(hi.left.value, int)
        and isinstance(hi.right, ast.Name)
    ):
        return Argnums(range(lo, hi.left.value), tail=max(lo, hi.left.value))
    return None


def literal_int_tuple(node: ast.AST) -> Optional[Argnums]:
    """Resolve a donate/static argnums expression: an int, a tuple/list
    of ints, ``tuple(range(...))`` (see :func:`_int_range`), or a sum
    of those. Anything else returns None (the rule then skips the site
    rather than guessing)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return Argnums((node.value,))
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
            else:
                return None
        return Argnums(out)
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        left = literal_int_tuple(node.left)
        right = literal_int_tuple(node.right)
        return None if left is None or right is None else left | right
    if (
        isinstance(node, ast.Call) and dotted(node.func) == "tuple"
        and len(node.args) == 1 and isinstance(node.args[0], ast.Call)
        and dotted(node.args[0].func) == "range"
    ):
        return _int_range(node.args[0])
    return None


def jit_call_argnums(call: ast.Call, kw: str) -> Optional[Argnums]:
    """``donate_argnums``/``static_argnums`` of a ``jax.jit(...)`` or
    ``partial(jax.jit, ...)`` call, if literal."""
    for k in call.keywords:
        if k.arg == kw:
            return literal_int_tuple(k.value)
    return None


def is_jit_call(call: ast.Call) -> bool:
    name = dotted(call.func)
    if name in JIT_NAMES:
        return True
    # partial(jax.jit, ...)
    if name in PARTIAL_NAMES and call.args:
        return dotted(call.args[0]) in JIT_NAMES
    return False


def decorator_donate_argnums(fn: ast.FunctionDef) -> Optional[Argnums]:
    """donate_argnums from ``@partial(jax.jit, donate_argnums=...)`` /
    ``@jax.jit(donate_argnums=...)`` decorators; None when absent or
    unresolvable."""
    for dec in fn.decorator_list:
        if isinstance(dec, ast.Call) and is_jit_call(dec):
            nums = jit_call_argnums(dec, "donate_argnums")
            if nums:
                return nums
    return None


def decorator_is_jitted(fn: ast.FunctionDef) -> bool:
    """True if the function is jitted by decoration, with or without
    options (``@jax.jit`` bare, or ``@partial(jax.jit, ...)``)."""
    for dec in fn.decorator_list:
        if dotted(dec) in JIT_NAMES:
            return True
        if isinstance(dec, ast.Call) and is_jit_call(dec):
            return True
    return False


def walk_no_nested_functions(node: ast.AST) -> Iterator[ast.AST]:
    """Walk a function body WITHOUT descending into nested def/lambda
    (their bodies run at another time, under other rules)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if isinstance(
            n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef)
        ):
            continue
        stack.extend(ast.iter_child_nodes(n))


def self_attr(node: ast.AST) -> Optional[str]:
    """'x' for a ``self.x`` attribute node, else None."""
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


def call_names(body: Sequence[ast.stmt]) -> Set[str]:
    """Names of all functions called anywhere under the statements."""
    out: Set[str] = set()
    for stmt in body:
        for n in ast.walk(stmt):
            if isinstance(n, ast.Call):
                d = dotted(n.func)
                if d:
                    out.add(d)
    return out
