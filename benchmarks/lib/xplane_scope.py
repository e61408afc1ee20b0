"""Self seconds of a trace's device operations by named scope.

``lib/xplane.py`` classes operations by opcode; the program's named scopes
(``step.gradients``, ``rank.pairwise``, ``wave.*``) ride elsewhere: in the
event METADATA's stat ``tf_op``, the operation's name path (for example
``jit(step)/step.gradients/rank.pairwise/while/body/sort``), which
``jax.profiler.ProfileData`` does not expose. This file reads the xplane
proto itself, with the generated module the installed TensorFlow wheel
ships (``tensorflow/tsl/profiler/protobuf/xplane_pb2.py``), loaded by its
path so that ``tensorflow`` itself is never imported; where that module is
not installed, or the trace holds no ``tf_op``, every function returns None.

Time is ``lib/xplane.py``'s: an event's self time (its duration less its
children's on the same line), over the events that overlap the window.
"""
import functools
import importlib.util
import os

from . import xplane

SCOPE_STAT = "tf_op"


@functools.lru_cache(maxsize=1)
def _pb2():
    """The xplane proto module, or None."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.origin:
        return None
    path = os.path.join(os.path.dirname(spec.origin), "tsl", "profiler", "protobuf",
                        "xplane_pb2.py")
    if not os.path.exists(path):
        return None
    load = importlib.util.spec_from_file_location("bench_xplane_pb2", path)
    module = importlib.util.module_from_spec(load)
    load.loader.exec_module(module)
    return module


def scoped_events(path: str, pb2=None):
    """{device plane: [[name, category, scope path, start_ns, dur_ns], ...]}
    of the operation line, or None without the proto module. ``scope path``
    is the ``tf_op`` stat of the event's metadata ("" where it has none)."""
    pb2 = pb2 or _pb2()
    if pb2 is None:
        return None
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        scope_of = {}
        for mid, md in plane.event_metadata.items():
            for st in md.stats:
                if stat_names.get(st.metadata_id) == SCOPE_STAT:
                    scope_of[mid] = (st.str_value if st.WhichOneof("value") == "str_value"
                                     else stat_names.get(st.ref_value, ""))
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            events = []
            for e in line.events:
                md = plane.event_metadata[e.metadata_id]
                text = md.name if " = " in md.name else (md.display_name or md.name)
                name, category = xplane.parse_hlo(text)
                start = line.timestamp_ns * 1.0 + e.offset_ps / 1000.0
                events.append([name, category, scope_of.get(e.metadata_id, ""),
                               start, e.duration_ps / 1000.0])
            out[plane.name] = events
    return out


def in_scope(scope_path: str, scope: str) -> bool:
    return scope in scope_path.split("/")


def scope_seconds(events, window, scopes):
    """{scope: self seconds of the operations under it} inside ``window`` =
    (start_ns, end_ns) under ``seconds``, for each scope its top operations
    by self seconds under ``ops``, and the scope paths that took the most
    seconds under ``paths``. ``events`` is one plane's list from
    ``scoped_events``."""
    w0, w1 = window
    events = [e for e in events if e[3] + e[4] > w0 and e[3] < w1]
    # xplane._self_times reads [name, category, start, dur]
    _, self_ns, _ = xplane._self_times([[e[0], e[1], e[3], e[4]] for e in events])
    seconds = {s: 0.0 for s in scopes}
    ops = {s: {} for s in scopes}
    tagged, paths = 0, {}
    for e, ns in zip(events, self_ns):
        if e[2]:
            tagged += 1
            paths[e[2]] = paths.get(e[2], 0.0) + max(ns, 0.0) / 1e9
        for s in scopes:
            if in_scope(e[2], s):
                seconds[s] += max(ns, 0.0) / 1e9
                key = f"{e[1] or '-'}:{xplane._base_name(e[0])}"
                ops[s][key] = ops[s].get(key, 0.0) + max(ns, 0.0) / 1e9
    if not tagged:
        return None
    return {"seconds": seconds,
            "ops": {s: sorted(([k, v] for k, v in o.items()), key=lambda kv: -kv[1])[:12]
                    for s, o in ops.items()},
            "paths": sorted(([k, v] for k, v in paths.items()), key=lambda kv: -kv[1])[:40]}


def reduce_scopes(path: str, recorded: dict, scopes, annotation: str = "bench.traced"):
    """The first device plane's ``scope_seconds`` in the window that
    ``lib/xplane.reduce_events`` takes (the host annotation ``annotation``
    where it was recorded, else the plane's first to last operation); None
    where the trace names no scope."""
    planes = scoped_events(path)
    if not planes:
        return None
    plane = sorted(planes)[0]
    events = planes[plane]
    if not events:
        return None
    spans = [(s, s + d) for n, s, d in recorded.get("host", []) if n == annotation]
    if spans:
        window = (min(s for s, _ in spans), max(e for _, e in spans))
    else:
        window = (min(e[3] for e in events), max(e[3] + e[4] for e in events))
    return scope_seconds(events, window, scopes)
