"""Structural walks over jaxprs and optimized-HLO text.

This is the ONE implementation of the recursive jaxpr walk the repo used
to carry as per-test helpers (`_jaxpr_has_sort` of the wave-loop tests,
`_jaxpr_has_primitive` in test_efb_bundlespace) — those are deleted; both the trace-lint tier and
the tests assert through these functions. No jax import: everything here
is duck-typed over ``.eqns`` / ``.jaxpr`` attributes, so the module loads
in the dependency-free AST tier too.
"""
from __future__ import annotations

import re
from typing import Iterable, Iterator, Optional, Set

_LOOP_PRIMS = {"while", "scan"}


def _inner_jaxprs(params: dict) -> Iterator:
    for v in params.values():
        for j in (v if isinstance(v, (list, tuple)) else [v]):
            inner = getattr(j, "jaxpr", None)
            if inner is not None:
                yield inner
            elif hasattr(j, "eqns"):
                yield j


def iter_eqns(jaxpr) -> Iterator:
    """Every equation in ``jaxpr`` including all sub-jaxprs carried in eqn
    params (while/scan/cond bodies, pjit/shard_map calls, custom calls)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)   # accept ClosedJaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _inner_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def primitive_names(jaxpr) -> Set[str]:
    return {eqn.primitive.name for eqn in iter_eqns(jaxpr)}


def has_primitive(jaxpr, name: str) -> bool:
    return any(eqn.primitive.name == name for eqn in iter_eqns(jaxpr))


def count_primitive(jaxpr, name: str) -> int:
    return sum(1 for eqn in iter_eqns(jaxpr)
               if eqn.primitive.name == name)


def loop_body_eqns(jaxpr) -> Iterator:
    """Equations living INSIDE while/scan bodies (any nesting depth) —
    the per-iteration cost surface."""
    return (eqn for eqn, _ in loop_body_eqns_in_cond(jaxpr))


def loop_body_eqns_in_cond(jaxpr, _in_loop: bool = False,
                           _in_cond: bool = False) -> Iterator:
    """``(eqn, in_cond)`` for every equation inside a while/scan body;
    ``in_cond`` says the equation sits in a branch of a ``cond`` that is
    itself inside the loop — work only SOME iterations pay."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if _in_loop:
            yield eqn, _in_cond
        for sub in _inner_jaxprs(eqn.params):
            yield from loop_body_eqns_in_cond(
                sub, _in_loop or name in _LOOP_PRIMS,
                _in_cond or (_in_loop and name == "cond"))


def leading_dims(eqn) -> Set[int]:
    """Leading dimension of every array operand of ``eqn``."""
    out: Set[int] = set()
    for v in eqn.invars:
        shape = getattr(getattr(v, "aval", None), "shape", None)
        if shape:
            out.add(int(shape[0]))
    return out


def out_dtype_names(jaxpr) -> Set[str]:
    """dtype names of every equation output var across the program."""
    out: Set[str] = set()
    for eqn in iter_eqns(jaxpr):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None:
                out.add(str(dt))
    return out


# content = non-brace runs interleaved with complete one-level brace
# groups ({0}, {}), so the capture spans the whole alias map and stops at
# ITS closing brace, not the first nested one
_ALIAS_HEADER = re.compile(r"input_output_alias=\{((?:[^{}]|\{[^{}]*\})*)\}")
_ALIAS_ENTRY = re.compile(r"\{[\d,\s]*\}:\s*\(")


def hlo_alias_count(hlo_text: str) -> int:
    """Number of input/output alias pairs in an HloModule header —
    ``input_output_alias={ {0}: (0, {}, may-alias), {1}: (2, {}, ...) }``.
    0 when the header is absent (donation requested but discarded)."""
    m = _ALIAS_HEADER.search(hlo_text)
    if not m:
        return 0
    return len(_ALIAS_ENTRY.findall(m.group(1)))
