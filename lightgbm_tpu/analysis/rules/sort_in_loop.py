"""R007: full-array argsort/sort inside a lax.while_loop body.

A sort inside the device-side wave loop is a fixed cost of every trip that
reaches it, so each one has to be a measured decision. The grower holds
exactly one: a wave whose histogram pass is COMPACTED builds its
slot-grouped row index with one stable sort of the rows by pending slot,
inside that arm of the wave's ``lax.cond`` (grower.py, phase
``wave.partition``); a streamed wave sorts nothing. On the v5e that sort is
the cheapest row-sized pass there is (1.9 ns a row at 14.7M rows, against
8.6 for one element gather and 4.9 for a scatter — which the TPU's compiler
expands into a sort of its own, unseen at this level: PERF.md, PR 27/28),
and it replaced a "sort-free" carried partition that cost half the tree.
New sorts must not creep into loop bodies unmeasured, and none belongs
outside a ``cond`` arm that only some waves take.

Detection is a reachability walk over the whole-package call graph
(``common.PackageIndex``): functions passed to ``lax.while_loop`` (by name
or inline lambda) anywhere in the lint run are roots; any function they
reference — called directly, through an imported module object, via a
``self.`` method, or passed onward to e.g. ``lax.cond`` — is reachable,
across module boundaries; a ``jnp.argsort``/``jnp.sort``/``jnp.lexsort``/
``lax.sort``/``lax.sort_key_val`` call in reachable code fires. Linting a
single file degrades to the historical same-file walk. The audited site —
the grower's compacted arm — lives in the committed baseline
(``tpu_lint_baseline.json``; trace contract T001 and
tests/test_named_scopes.py count the sorts of the traced and of the compiled
program); deliberate small-axis sorts (categorical bin ordering, voting gain ranks)
carry inline waivers at the call site.
"""
from __future__ import annotations

import ast

from .common import dotted_name, reachable_loop_code

RULE_ID = "R007"

_WHILE_LOOP = frozenset({"jax.lax.while_loop", "lax.while_loop"})
_SORT_CALLS = {
    "jnp.argsort", "jnp.sort", "jnp.lexsort",
    "jax.numpy.argsort", "jax.numpy.sort", "jax.numpy.lexsort",
    "jax.lax.sort", "lax.sort",
    "jax.lax.sort_key_val", "lax.sort_key_val",
}


class SortInLoopRule:
    rule_id = RULE_ID
    cross_module = True   # findings depend on the whole-package call graph
    summary = ("argsort/sort reachable from a lax.while_loop body — a "
               "fixed cost of every trip that reaches it; keep it inside a "
               "cond arm, measure it on the chip, and baseline the site")

    def check(self, ctx):
        reported = set()
        for fn in reachable_loop_code(ctx, _WHILE_LOOP):
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) \
                        and dotted_name(node.func) in _SORT_CALLS \
                        and id(node) not in reported:
                    reported.add(id(node))
                    where = getattr(fn, "name", "<lambda>")
                    yield ctx.finding(
                        self.rule_id, node,
                        f"`{dotted_name(node.func)}` reachable from a "
                        f"lax.while_loop body (via `{where}`) — a fixed "
                        f"cost of every trip that reaches it; keep it in a "
                        f"cond arm only some trips take, measure it on the "
                        f"chip, and baseline the audited site")
