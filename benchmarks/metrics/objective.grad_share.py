"""Share of the traced tree's busy device seconds spent in the objective:
the self seconds of the operations under the named scope ``step.gradients``
(``lib/xplane_scope.py``: the scope path of each operation, from the event
metadata's ``tf_op``), over the tree's busy seconds (``lib/xplane.py``), in
%. In a ranking cell that is the pair blocks (``rank.pairwise``), the
per-query sort and scatter, and the gathers in and out of the query layout.
None in an untraced run, or where the trace names no scope."""


def read(run: dict):
    t = run.get("trace")
    if not t or not t.get("busy_s") or not t.get("scopes"):
        return None
    return 100.0 * t["scopes"]["seconds"]["step.gradients"] / t["busy_s"]
