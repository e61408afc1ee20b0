"""Reduction of a profiler trace to the numbers the per-layer metrics read.

Two stages, so that the second can be checked on a recorded trace
(benchmarks/tests/test_trace_reduction.py):

  1. ``device_events(path)``: the ``.xplane.pb`` -> per device plane the list
     of operation events ``(name, category, start_ns, duration_ns)`` of its
     "XLA Ops" line, and the host's ``bench.*`` annotations.
  2. ``reduce_events(...)``: events -> busy union, self time per class of
     operation, the top operations, and the longest idle gaps.

Device operations nest: a ``while`` spans its body's operations. Time is
counted ONCE, as self time (an event's duration minus its children's), and
"busy" is the union of the LEAF operations' intervals: a loop that waits
between two body operations is not busy there.

Classes, by what the trace itself records (the program has no named scopes).
On this chip an event's name is the text of its HLO instruction, and stage 1
keeps of it the instruction's name, its opcode and, for a fusion, its kind
(``fusion/kOutput``), for a custom call its target:
  matmul   opcode ``convolution`` or ``dot``, or a fusion of kind ``kOutput``
           or ``kConvolution``: the TPU compiler fuses a dot's consumers into
           the dot's OUTPUT, and the one-hot histogram pass is such a fusion
           (``f32[F,B,S*ch] fusion(acc, s32[chunk,F] codes, bf16[chunk,S*ch])``)
  custom   a ``custom-call`` whose target is ``tpu_custom_call``: a
           Pallas/Mosaic kernel. (A fusion of kind ``kCustom`` is NOT one:
           it is the compiler's own gather/scatter fusion, and is "other")
  other    everything else: gathers, scatters, sorts, selects, copies, the
           loop bodies' own time
"""
import gzip
import json
import re

OPS_LINE = "XLA Ops"
MATMUL_OPCODES = ("convolution", "dot")
MATMUL_FUSIONS = ("fusion/kOutput", "fusion/kConvolution")
KERNEL_TARGET = "tpu_custom_call"
_OPCODE = re.compile(r"\b([a-z][a-z0-9_-]*)\(")
_KIND = re.compile(r"\bkind=(\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
# only the longest gaps are named one by one; the rest are summed
NAMED_GAPS = 64


def parse_hlo(text: str):
    """(instruction name, category) of an event named by its HLO text:
    ``%select_add_fusion.4 = f32[..] fusion(..), kind=kOutput, calls=..`` ->
    ("select_add_fusion.4", "fusion/kOutput"). A plain name passes through
    with an empty category."""
    if " = " not in text:
        return text.lstrip("%"), ""
    name, rhs = text.split(" = ", 1)
    m = _OPCODE.search(rhs)          # shapes hold T(..), S(..): never lower case
    opcode = m.group(1) if m else ""
    if opcode == "fusion":
        k = _KIND.search(rhs)
        opcode += "/" + (k.group(1) if k else "")
    elif opcode == "custom-call":
        t = _TARGET.search(rhs)
        opcode += "/" + (t.group(1) if t else "")
    return name.strip().lstrip("%"), opcode


def classify(name: str, category: str) -> str:
    if category == "custom-call/" + KERNEL_TARGET:
        return "custom"
    if category in MATMUL_FUSIONS or category.split("/")[0] in MATMUL_OPCODES:
        return "matmul"
    return "other"


def device_events(path: str) -> dict:
    """{"devices": {plane name: [[name, category, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]} from an xplane file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                events = []
                for e in line.events:
                    name, category = parse_hlo(e.name)
                    events.append([name, category, float(e.start_ns),
                                   float(e.duration_ns)])
                devices[plane.name] = events
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, float(e.start_ns), float(e.duration_ns)])
    return {"devices": devices, "host": host}


def _self_times(events):
    """(events' order by start, longer first; self ns per event; whether each
    is a leaf). Children lie inside their parent on the same line."""
    order = sorted(range(len(events)), key=lambda i: (events[i][2], -events[i][3]))
    self_ns = [events[i][3] for i in range(len(events))]
    leaf = [True] * len(events)
    stack = []
    for i in order:
        start, end = events[i][2], events[i][2] + events[i][3]
        while stack and events[stack[-1]][2] + events[stack[-1]][3] <= start:
            stack.pop()
        if stack and end <= events[stack[-1]][2] + events[stack[-1]][3]:
            self_ns[stack[-1]] -= events[i][3]
            leaf[stack[-1]] = False
        stack.append(i)
    return order, self_ns, leaf


def _union(intervals):
    """(total length, merged intervals) of [start, end) pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _base_name(name: str) -> str:
    """``fusion.123`` -> ``fusion``: one row per kind of operation."""
    head = name.split(" ")[0].lstrip("%")
    stem, dot, tail = head.rpartition(".")
    return stem if dot and tail.isdigit() else head


def reduce_device(events, window, host):
    """One device's numbers inside ``window`` = (start_ns, end_ns)."""
    w0, w1 = window
    events = [e for e in events if e[2] + e[3] > w0 and e[2] < w1]
    order, self_ns, leaf = _self_times(events)
    busy_ns, merged = _union(
        [(max(events[i][2], w0), min(events[i][2] + events[i][3], w1))
         for i in order if leaf[i]])
    by_class = {"matmul": 0.0, "custom": 0.0, "other": 0.0}
    counts = {"matmul": 0, "custom": 0, "other": 0}
    by_op = {}
    for i in order:
        name, category = events[i][0], events[i][1]
        cls = classify(name, category)
        by_class[cls] += max(self_ns[i], 0.0)
        counts[cls] += 1
        key = f"{cls}:{category or '-'}:{_base_name(name)}"
        by_op[key] = by_op.get(key, 0.0) + max(self_ns[i], 0.0)
    # idle gaps between leaf operations, named by the device operation that
    # encloses the gap, else by the host annotation open at its middle
    edges = [[w0, w0]] + merged + [[w1, w1]]
    raw = sorted(((b[0] - a[1], (a[1] + b[0]) / 2)
                  for a, b in zip(edges[:-1], edges[1:]) if b[0] > a[1]),
                 reverse=True)
    containers = [i for i in order if not leaf[i]]
    gaps = []
    for ns, mid in raw[:NAMED_GAPS]:
        name = None
        for i in containers:            # innermost enclosing container wins
            if events[i][2] <= mid < events[i][2] + events[i][3]:
                name = "device:inside " + _base_name(events[i][0])
        if name is None:
            for hname, hs, hd in host:
                if hs <= mid < hs + hd:
                    name = "host:" + hname
            name = name or "host:unattributed"
        gaps.append((name, ns))
    if raw[NAMED_GAPS:]:
        gaps.append(("shorter gaps, not named", sum(ns for ns, _ in raw[NAMED_GAPS:])))
    by_gap = {}
    for name, ns in gaps:
        by_gap[name] = by_gap.get(name, 0.0) + ns
    return {"busy_s": busy_ns / 1e9, "window_s": (w1 - w0) / 1e9,
            "class_s": {k: v / 1e9 for k, v in by_class.items()},
            "class_events": counts, "n_events": len(events),
            "ops": sorted(([k, v / 1e9] for k, v in by_op.items()),
                          key=lambda kv: -kv[1]),
            "gaps": sorted(([k, v / 1e9] for k, v in by_gap.items()),
                           key=lambda kv: -kv[1]),
            "longest_gap_s": (raw[0][0] if raw else 0.0) / 1e9}


def reduce_events(recorded: dict, annotation: str = "bench.traced") -> dict:
    """All devices' numbers, averaged over the devices that ran operations.
    The window is the host annotation ``annotation`` where it was recorded,
    else the span from the first to the last device operation."""
    host = recorded.get("host", [])
    spans = [(s, s + d) for n, s, d in host if n == annotation]
    per_device = {}
    for plane, events in recorded["devices"].items():
        if not events:
            continue
        if spans:
            window = (min(s for s, _ in spans), max(e for _, e in spans))
        else:
            window = (min(e[2] for e in events), max(e[2] + e[3] for e in events))
        per_device[plane] = reduce_device(events, window, host)
    if not per_device:
        return {"devices": 0}
    n = len(per_device)
    first = next(iter(per_device.values()))
    mean = lambda f: sum(f(d) for d in per_device.values()) / n   # noqa: E731
    return {"devices": n,
            "busy_s": mean(lambda d: d["busy_s"]),
            "window_s": mean(lambda d: d["window_s"]),
            "class_s": {k: mean(lambda d, k=k: d["class_s"][k]) for k in first["class_s"]},
            "class_events": first["class_events"], "n_events": first["n_events"],
            "ops": first["ops"], "gaps": first["gaps"],
            "longest_gap_s": first["longest_gap_s"]}


def save_events(recorded: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(recorded, f)


def load_events(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)
