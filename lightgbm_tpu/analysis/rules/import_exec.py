"""R006: jnp./jax. execution at module import time.

A ``jnp.``/``jax.random.``/``jax.lax.`` call at module scope initializes
the backend the moment the module is imported — before the process had a
chance to pick a platform (JAX_PLATFORMS), arm the hermetic-CPU guard
(utils/hermetic.py), or point the compile cache somewhere useful. A chip
belongs to one process at a time, so an import-time backend grab takes the
TPU away from every other process on the host — including in parents that
only meant to launch the child that needs it.
Constants like ``jnp.inf``/``jnp.float32`` are attribute reads, not calls,
and stay fine; build arrays lazily inside the function that needs them.

``if __name__ == "__main__":`` blocks run at script time, not import, and
are exempt.
"""
from __future__ import annotations

import ast

from .common import dotted_name

RULE_ID = "R006"

_EXEC_PREFIXES = ("jnp.", "jax.numpy.", "jax.random.", "jax.lax.", "jax.nn.")
_EXEC_EXACT = {"jax.device_put", "jax.devices", "jax.local_devices",
               "jax.device_count", "jax.local_device_count",
               "jax.default_backend", "jax.block_until_ready"}


def _walk_skipping_functions(root):
    """ast.walk that never descends into function/lambda bodies — code in
    there runs at call time, not import time."""
    stack = [root]
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.Lambda, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _is_main_guard(stmt) -> bool:
    return (isinstance(stmt, ast.If)
            and isinstance(stmt.test, ast.Compare)
            and isinstance(stmt.test.left, ast.Name)
            and stmt.test.left.id == "__name__")


class ImportExecRule:
    rule_id = RULE_ID
    summary = ("jnp./jax. call executed at module import time (forces "
               "backend init before platform/cache setup)")

    def _walk_module_level(self, stmts):
        """Statements executed at import: module body, descending through
        If/Try/With/For/While and ClassDef bodies, but never into function
        or lambda bodies, and skipping `if __name__ == "__main__"`."""
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if _is_main_guard(stmt):
                continue
            yield stmt
            for attr in ("body", "orelse", "finalbody"):
                inner = getattr(stmt, attr, None)
                if inner:
                    yield from self._walk_module_level(inner)
            for h in getattr(stmt, "handlers", ()):
                yield from self._walk_module_level(h.body)

    def check(self, ctx):
        for stmt in self._walk_module_level(ctx.tree.body):
            if isinstance(stmt, (ast.If, ast.Try, ast.With, ast.For,
                                 ast.While, ast.ClassDef)):
                # children were yielded separately; only scan the parts of
                # this statement that are not child statements (tests,
                # with-items, iterables)
                exprs = []
                if isinstance(stmt, (ast.If, ast.While)):
                    exprs = [stmt.test]
                elif isinstance(stmt, ast.With):
                    exprs = [i.context_expr for i in stmt.items]
                elif isinstance(stmt, ast.For):
                    exprs = [stmt.iter]
            else:
                exprs = [stmt]
            for expr in exprs:
                for node in _walk_skipping_functions(expr):
                    if not isinstance(node, ast.Call):
                        continue
                    name = dotted_name(node.func) or ""
                    if name.startswith(_EXEC_PREFIXES) \
                            or name in _EXEC_EXACT:
                        yield ctx.finding(
                            self.rule_id, node,
                            f"`{name}(...)` runs at module import time — "
                            f"it initializes the jax backend before "
                            f"platform/hermetic/cache setup; build the "
                            f"value lazily inside the function that uses it")
