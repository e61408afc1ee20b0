"""Predicate checks for trace contracts.

Each check exposes ``run(program) -> list[str]`` where every failure
message starts with a stable kind token (``forbidden-primitive``,
``required-collective``, ``dtype``, ``donation``, ``host-transfer``,
``count``, ``row-pass``) — the token is the baseline fingerprint component, so message
wording can evolve without rotting baselines.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Tuple

from . import jaxpr_utils as ju
from .registry import TracedProgram

# collective_bytes() key prefix -> jaxpr primitive it lowers to
# (parallel/comm.py DataParallelComm: psum_root_scalars, psum_scatter_hist,
# allgather_splits)
COLLECTIVE_PRIMS: Dict[str, str] = {
    "psum_scatter": "reduce_scatter",
    "allgather": "all_gather",
    "all_gather": "all_gather",
    "psum": "psum",
    "all_reduce": "psum",
}
_KNOWN_COLLECTIVES = {"psum", "reduce_scatter", "all_gather", "all_to_all",
                      "ppermute"}

# host round-trip primitives that must never sit inside a device loop body
_HOST_PRIMS = {"device_put", "pure_callback", "io_callback",
               "debug_callback", "callback", "outside_call",
               "infeed", "outfeed"}


def _prefix_to_prim(key: str) -> Optional[str]:
    best = None
    for prefix, prim in COLLECTIVE_PRIMS.items():
        if key.startswith(prefix) and (best is None
                                       or len(prefix) > len(best[0])):
            best = (prefix, prim)
    return best[1] if best else None


class ForbidPrimitives:
    """Named primitives must not appear — anywhere, or (where="loops")
    only inside while/scan bodies."""

    def __init__(self, names: Iterable[str], where: str = "anywhere"):
        self.names = frozenset(names)
        self.where = where

    def run(self, p: TracedProgram):
        if self.where == "loops":
            present = {e.primitive.name for e in ju.loop_body_eqns(p.jaxpr)}
        else:
            present = ju.primitive_names(p.jaxpr)
        return [f"forbidden-primitive: `{n}` present in the traced program"
                f"{' (inside a loop body)' if self.where == 'loops' else ''}"
                for n in sorted(self.names & present)]


class RowPassesInLoops:
    """Row-sized passes a device loop may hold, per iteration. An operand
    is row-sized when its leading dimension is the program's ``rows``.

    - at most ``max_sorts`` row-sized ``sort`` equations inside loop
      bodies, each inside a ``cond`` branch (only the iterations that take
      the branch pay for it);
    - no ``scatter``, ``scatter-add`` or ``cumsum`` over a row-sized
      operand anywhere in a loop body: on the TPU a row-sized scatter
      expands to a sort of (index, value) pairs plus a gather-priced write,
      which no jaxpr-level check can see (PERF.md, PR 27/28), and a
      row-sized cumsum to a reduce-window."""

    forbid = frozenset({"scatter", "scatter-add", "cumsum"})

    def __init__(self, max_sorts: int = 1):
        self.max_sorts = max_sorts

    def run(self, p: TracedProgram):
        if not p.rows:
            return ["row-pass: contract target states no row count "
                    "(builder bug)"]
        sorts, bare, forbidden = 0, 0, set()
        for eqn, in_cond in ju.loop_body_eqns_in_cond(p.jaxpr):
            name = eqn.primitive.name
            if p.rows not in ju.leading_dims(eqn):
                continue
            if name == "sort":
                sorts += 1
                bare += not in_cond
            elif name in self.forbid:
                forbidden.add(name)
        out = []
        if sorts > self.max_sorts:
            out.append(f"row-pass: {sorts} row-sized `sort`s inside loop "
                       f"bodies, contract allows {self.max_sorts}")
        if bare:
            out.append(f"row-pass: {bare} row-sized `sort`(s) in a loop "
                       f"body outside any `cond` branch — every iteration "
                       f"pays for it")
        out += [f"row-pass: row-sized `{n}` inside a loop body — every "
                f"iteration pays a pass over all rows (a scatter also "
                f"hides a sort)" for n in sorted(forbidden)]
        return out


class RequiredCollectives:
    """The collective set the program's comm strategy promises — derived
    from ``comm.collective_bytes()`` key prefixes — must all appear in the
    jaxpr, and no collective outside that set may appear (an undeclared
    collective means ``collective_bytes`` under-reports interconnect
    traffic, breaking the bench's cost model)."""

    def run(self, p: TracedProgram):
        if p.comm is None:
            return ["required-collective: contract target supplies no comm "
                    "object to derive the expected collective set from"]
        # builders hand either the collective_bytes() dict itself (the comm
        # methods take per-spec shape args) or a zero-arg callable
        declared = p.comm() if callable(p.comm) else p.comm
        expected = set()
        for key in declared:
            prim = _prefix_to_prim(str(key))
            if prim is not None:
                expected.add(prim)
        present = ju.primitive_names(p.jaxpr) & _KNOWN_COLLECTIVES
        out = []
        for prim in sorted(expected - present):
            out.append(f"required-collective: `{prim}` promised by "
                       f"collective_bytes() but absent from the program")
        for prim in sorted(present - expected):
            out.append(f"required-collective: undeclared collective "
                       f"`{prim}` in the program — collective_bytes() "
                       f"does not account for it")
        return out


class DtypeDiscipline:
    """No silent f64 upcasts: float64 may only appear when the shape class
    opted in (hist_f64 Kahan accumulation / host-side accumulation —
    neither traces through these entries)."""

    def __init__(self, forbid: Tuple[str, ...] = ("float64", "complex128")):
        self.forbid = tuple(forbid)

    def run(self, p: TracedProgram):
        present = ju.out_dtype_names(p.jaxpr)
        return [f"dtype: `{d}` value materialized in the traced program — "
                f"f64 belongs to hist_f64 Kahan sums and host accumulation "
                f"only" for d in sorted(set(self.forbid) & present)]


class DonationEffective:
    """Donated arguments must actually alias outputs in the compiled
    executable (HloModule ``input_output_alias`` header) — donation that
    XLA silently discards (shape mismatch, CPU gating bug, sharding
    conflict) re-introduces the full-carry copy per step."""

    def run(self, p: TracedProgram):
        if not p.donate_argnums:
            return ["donation: contract target requested no donation — "
                    "nothing to verify (builder bug)"]
        n = ju.hlo_alias_count(p.hlo_text())
        want = max(1, p.expected_aliases)
        if n < want:
            return [f"donation: only {n} input/output alias(es) in the "
                    f"compiled executable, expected >= {want} for "
                    f"donate_argnums={p.donate_argnums} — XLA dropped the "
                    f"donation and the carry copies every step"]
        return []


class NoHostTransferInLoops:
    """No host round-trip primitives (device_put, callbacks, infeed)
    inside while/scan bodies — a per-iteration host sync serializes the
    device loop."""

    def run(self, p: TracedProgram):
        present = {e.primitive.name for e in ju.loop_body_eqns(p.jaxpr)}
        return [f"host-transfer: `{n}` inside a device loop body — a "
                f"per-iteration host round-trip"
                for n in sorted(_HOST_PRIMS & present)]


class CountPrimitive:
    """A primitive must appear exactly ``expect`` times (e.g. ONE batched
    Cholesky in the linear-leaf solve — a second one means the solve leg
    was duplicated instead of batched)."""

    def __init__(self, name: str, expect: int):
        self.name = name
        self.expect = expect

    def run(self, p: TracedProgram):
        n = ju.count_primitive(p.jaxpr, self.name)
        if n != self.expect:
            return [f"count: `{self.name}` appears {n}x, contract pins "
                    f"exactly {self.expect}"]
        return []
