"""R002: implicit host-device sync in hot-path modules.

``np.asarray(x)`` / ``float(x)`` / ``x.item()`` / ``x.tolist()`` on a jax
array blocks on the device and pulls the value to the host, draining the
asynchronous dispatch queue. One of these inside
the per-iteration training path (``lightgbm_tpu/boosting/``, ``grower.py``,
``ops/``) silently serializes the pipeline every step. Hoist the sync out
of the loop, or keep the value on-device.

Scope: only functions in hot-path modules, and only receivers/arguments
that provably flow from a jnp./jax. expression — host-side numpy code in
the same files is untouched.

Waiver: a function decorated with ``@allowed_host_sync("<reason>")``
(lightgbm_tpu/robustness) is an *audited* sync point — the checkpoint state
fetch, the per-iteration nan_policy flag fetch — and is skipped entirely.
The decorator replaces inline ``# tpu-lint: disable=R002`` suppressions and
records WHY the sync is the contract, next to the code.
"""
from __future__ import annotations

import ast

from .common import (dotted_name, expr_is_traced, infer_traced_names,
                     iter_functions, jit_static_params, traced_entry_functions)

RULE_ID = "R002"

HOT_PATH_MARKERS = ("lightgbm_tpu/boosting/", "lightgbm_tpu/ops/")
HOT_PATH_FILES = ("grower.py", "efb.py")

_SYNC_CALLS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
               "float", "int", "bool"}
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}


def _is_hot_path(rel: str) -> bool:
    rel = rel.replace("\\", "/")
    if any(m in rel for m in HOT_PATH_MARKERS):
        return True
    return any(rel.endswith("/" + f) or rel == f for f in HOT_PATH_FILES)


def _has_sync_waiver(fn) -> bool:
    """True when ``fn`` carries the ``allowed_host_sync`` decorator (bare or
    dotted, always called with a reason string)."""
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = dotted_name(target)
        if name and name.split(".")[-1] == "allowed_host_sync":
            return True
    return False


class HostSyncRule:
    rule_id = RULE_ID
    summary = ("implicit host sync (np.asarray/float/.item()/.tolist()) on "
               "a jax array in a hot-path module")

    def check(self, ctx):
        if not _is_hot_path(ctx.rel):
            return
        jit_entries = {id(fn): static
                       for fn, static in traced_entry_functions(ctx.tree)}
        for fn in iter_functions(ctx.tree):
            if _has_sync_waiver(fn):
                continue
            params_traced = id(fn) in jit_entries
            traced = infer_traced_names(
                fn, params_traced=params_traced,
                static_params=jit_entries.get(id(fn), frozenset()))
            if not traced:
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func)
                if name in _SYNC_CALLS and node.args:
                    if expr_is_traced(node.args[0], traced):
                        yield ctx.finding(
                            self.rule_id, node,
                            f"`{name}()` on a traced/device value in "
                            f"hot-path function `{fn.name}` — implicit "
                            f"host sync; hoist it out of the iteration "
                            f"path or keep the value on-device")
                elif (isinstance(node.func, ast.Attribute)
                      and node.func.attr in _SYNC_METHODS
                      and node.func.attr != "block_until_ready"
                      and expr_is_traced(node.func.value, traced)):
                    yield ctx.finding(
                        self.rule_id, node,
                        f"`.{node.func.attr}()` on a traced/device value "
                        f"in hot-path function `{fn.name}` — implicit "
                        f"host sync")
