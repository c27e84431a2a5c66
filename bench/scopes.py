"""From the compiled step's HLO to the program's named scopes.

The program names the layers of its step with ``jax.named_scope``: each
name starts with ``fed.`` (``fed.oracle``, ``fed.oracle_g``,
``fed.unflatten``, ``fed.flatten``, ``fed.update``, ``fed.reduce``).  A
scope reaches the compiled program only as the ``op_name`` of an
instruction's ``metadata``, a path such as
``jit(train_step)/fed.oracle/vmap(fed.oracle_g)/jvp(transpose(jvp()))/dot_general``:
JAX wraps a scope in the transformations it was traced under.
:func:`scopes` strips the wrappers and keeps the ``fed.`` segments,
outermost first.  A fusion carries the metadata of its root; one that
carries none takes its fused root's.  An op that XLA made in a loop,
branch or call body without metadata takes the scope of the op that runs
that body; the ops it made at the top level, with no such op to name
them, stay unscoped.

A trace's op event is named by its instruction (``%name``), so
:func:`scope_map` of the compiled HLO text maps each op of a
:class:`bench.trace.Trace` to its scopes.  :func:`ms` is the device time
per step of the ops a predicate of their scopes selects; the ops that only
hold other ops (``while``, ``conditional``, ``call``) are never counted.
:data:`METRICS` names the per-layer quantities the scopes give.

No metric of ``BENCHMARK.json`` reads them yet: the per-layer readers'
context (``bench/run.py``) carries the compiled step's matrix ops, not its
HLO text.  Given that text, a reader ``bench/layers/<name>.py`` returns
``ms(trace, ctx.get("hlo"), METRICS[name])``.
"""
from __future__ import annotations

import functools
import re

from bench.trace import _CALLS, _COMPUTATION, CONTAINERS, parse

FED = "fed."
#: the top-level scopes of the step; what lies under none of them is
#: "unscoped"
TOP = ("fed.oracle", "fed.unflatten", "fed.flatten", "fed.update",
       "fed.reduce")
_WRAPPED = re.compile(r"^[\w.-]*\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT )?(%?[\w.-]+) = ")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
#: the bodies a loop, a branch or a call runs
_BODIES = re.compile(r"(?:body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([\w.-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def unwrap(segment: str) -> str:
    """``transpose(jvp(fed.oracle))`` → ``fed.oracle``; ``vmap(jvp())`` →
    ``""``."""
    m = _WRAPPED.match(segment)
    while m:
        segment = m.group(1)
        m = _WRAPPED.match(segment)
    return segment


def scopes(op_name: str) -> tuple:
    """The ``fed.`` segments of an ``op_name`` path, outermost first."""
    return tuple(s for s in map(unwrap, op_name.split("/"))
                 if s.startswith(FED))


def top(path: tuple):
    """The outermost scope of ``path``, or None where it has none."""
    return path[0] if path else None


@functools.lru_cache(maxsize=2)
def scope_map(hlo_text: str) -> dict:
    """``{"%name": scopes}`` for every instruction of every computation of
    a compiled program's HLO text (``()`` where its metadata names no
    ``fed.`` scope)."""
    own, calls, roots, comp, runner, cur = {}, {}, {}, {}, {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1) if m.group(1).startswith("%") else "%" + m.group(1)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else None
        comp[name] = cur
        if line.lstrip().startswith("ROOT ") and cur is not None:
            roots[cur] = name
        if parse(line.strip().removeprefix("ROOT "))[1].startswith("fusion"):
            c = _CALLS.search(line)
            if c:
                calls[name] = c.group(1)
        bodies = _BODIES.findall(line) + [
            b for group in _BRANCHES.findall(line)
            for b in re.findall(r"[\w.-]+", group)]
        for body in bodies:
            runner.setdefault(body, name)
    path = {}
    for name, op_name in own.items():
        if op_name is None and name in calls:
            op_name = own.get(roots.get(calls[name]))
        path[name] = scopes(op_name or "")
    out = {}
    for name, p in path.items():
        seen, up = set(), runner.get(comp[name])
        while not p and up is not None and up not in seen:
            seen.add(up)
            p, up = path[up], runner.get(comp[up])
        out[name] = p
    return out


#: per-layer quantities, each the selection of an op's scopes that
#: :func:`ms` sums: the hypergradient oracle at both STORM points; every
#: differentiation of g; the flatten/unflatten boundary (the outermost
#: scope, so an oracle inside it would not count); the client reduction,
#: averaged over the steps that communicate and those that do not; and
#: what the scopes miss
METRICS = {
    "oracle.ms": lambda path: "fed.oracle" in path,
    "oracle.g_ms": lambda path: "fed.oracle_g" in path,
    "flat.boundary_ms": lambda path: top(path) in ("fed.unflatten",
                                                   "fed.flatten"),
    "reduce.ms": lambda path: "fed.reduce" in path,
    "step.unscoped_ms": lambda path: not path,
}


def ms(trace, hlo, keep):
    """Device time per step, in ms, of the non-container ops whose scopes
    ``keep(scopes)`` selects: the union of their intervals averaged over
    the devices (:meth:`bench.trace.Trace.op_ns`) over the traced steps.
    ``hlo`` is the compiled step's HLO text.  None where there is none,
    where no instruction of it lies under a ``fed.`` scope (a program
    without the scopes), or where the selected ops took no time."""
    if not hlo:
        return None
    where = scope_map(hlo)
    if not any(where.values()):
        return None
    ns = trace.op_ns(lambda op: op.category not in CONTAINERS
                     and keep(where.get(op.name, ())))
    if ns <= 0:
        return None
    return ns / trace.steps / 1e6
