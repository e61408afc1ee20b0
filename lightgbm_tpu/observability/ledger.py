"""Perf regression ledger: normalized BENCH/MULTICHIP history + compare.

The repo's measured trajectory lives in checked-in ``BENCH_r<N>.json`` /
``MULTICHIP_r<N>.json`` files whose schemas grew organically (round 1 is a
raw harness wrapper with ``parsed: null``, round 2 a bare payload, round 5
a full phase report). This module normalizes that history into ONE
machine-readable ledger (``PERF_LEDGER.json``) and answers the question no
PR could answer before: *did this change regress a number we already
banked?*

- ``build_ledger()`` — rebuild the ledger from the checked-in files; the
  one-shot ``python -m lightgbm_tpu.observability.ledger --rebuild`` keeps
  the committed ledger from ever drifting from history (``--check`` fails
  when it has).
- ``compare(candidate, entries)`` — flag regressions of a fresh bench
  payload against best-known values: throughput (per platform/rows/kernel
  comparability key; serving entries additionally key on the ``|serve=``
  load shape), post-warm-up recompiles, headline host syncs, peak HBM,
  serving p99 latency, and compiled cost-model drift (FLOPs / bytes
  accessed, when both sides carry cost reports). ``bench.py --compare``
  wraps this and exits nonzero on any flag; ``make bench-diff`` wires it
  into ``make verify``.

Deliberately dependency-free (stdlib + the jax-free sibling
``costs.drift`` for the one shared band check) and deterministic (no
timestamps): rebuilding from the same files yields byte-identical output,
so the committed ledger is diffable and the ``--check`` mode is a plain
equality.
"""
from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

LEDGER_FILE = "PERF_LEDGER.json"
_ROUND_RE = re.compile(r"_r(\d+)\.json$")

# relative tolerances for compare(): generous enough to absorb run-to-run
# noise, tight enough that a real regression (the 2x
# cost of an extra full-N pass; a 20%+ throughput loss) always trips
DEFAULT_TOLERANCES = {
    "throughput": 0.15,       # value may sit up to 15% below best-known
    "hbm": 0.15,              # peak HBM may grow up to 15%
    "cost": 0.35,             # flops/bytes drift band vs recorded reports
    # serving p99 latency may sit up to this far ABOVE the best-known
    # floor: tail latency on a shared CI box is far noisier than
    # throughput, so the band is wide — a real regression (an extra
    # dispatch, a recompile in the loop) moves p99 by integer factors
    "p99": 0.75,
    # serve-chaos shed-rate ceiling: under the SAME offered overload the
    # shed fraction may sit this far (relative) above best-known plus a
    # 0.05 absolute allowance — shedding much more at equal load means
    # serving capacity regressed even if measured rows/s held
    "shed": 0.5,
    # chaos-dist recovery bands: fleet MTTR and peer-loss detection
    # latency are wall-clock of process relaunch + jit compile on a shared
    # CI box, so the bands are very wide (100% relative) — they exist to
    # catch order-of-magnitude regressions (a lost heartbeat probe turning
    # detection from ms into the full lease timeout; a resume path that
    # silently retrains from scratch), not run-to-run noise
    "mttr": 1.0,
    "detect": 1.0,
}


def _round_of(path: str) -> Optional[int]:
    m = _ROUND_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else None


def payload_of(path: str) -> Optional[Dict]:
    """Extract the result payload from a history file: either a bare bench
    JSON or the driver wrapper holding it under ``parsed``."""
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, ValueError):
        return None
    if not isinstance(doc, dict):
        return None
    if "parsed" in doc and "metric" not in doc:
        return doc["parsed"] if isinstance(doc["parsed"], dict) else None
    return doc


# ------------------------------------------------------------- normalization

def normalize_bench(payload: Optional[Dict], source: str,
                    round_: Optional[int]) -> Dict:
    """One BENCH payload -> the normalized ledger entry schema. Missing
    fields stay ``None`` — old rounds simply carry less signal."""
    e: Dict = {"source": source, "round": round_, "kind": "bench",
               "value": None, "unit": None, "vs_baseline": None,
               "platform": None, "rows": None, "kernel": None,
               "n_devices": None, "residency": None, "tree_batch": None,
               "auc": None, "serve": None, "serve_chaos": None,
               "chaos_dist": None, "bundle": None, "linear": None,
               "shed_rate": None, "p99_ms": None,
               "fleet_mttr_s": None, "detect_p50_ms": None,
               "detect_p99_ms": None, "shed_epochs": None,
               "recompiles_post_warmup": None, "host_syncs": None,
               "steady_s_per_iter": None, "hbm_peak_gb": None,
               "ingest": None, "identical_to_host": None,
               "cost": None, "error": None}
    if not payload:
        e["error"] = "unparseable history file"
        return e
    for k in ("value", "unit", "vs_baseline", "platform", "rows", "kernel",
              "n_devices", "residency", "tree_batch", "auc", "serve",
              "serve_chaos", "chaos_dist", "bundle", "linear", "shed_rate",
              "p99_ms", "fleet_mttr_s", "detect_p50_ms", "detect_p99_ms",
              "shed_epochs", "recompiles_post_warmup", "hbm_peak_gb",
              "ingest", "identical_to_host", "error"):
        if payload.get(k) is not None:
            e[k] = payload[k]
    head = (payload.get("phase_timings") or {}).get("headline") or {}
    if head.get("host_syncs") is not None:
        e["host_syncs"] = head["host_syncs"]
    if head.get("steady_s_per_iter") is not None:
        e["steady_s_per_iter"] = head["steady_s_per_iter"]
    cost = (payload.get("telemetry") or {}).get("cost_reports") \
        or payload.get("cost_reports")
    if cost:
        # keep only the drift-comparable numerics per site
        e["cost"] = {site: {f: r.get(f) for f in
                            ("flops", "bytes_accessed", "peak_hbm_bytes")
                            if r.get(f) is not None}
                     for site, r in cost.items() if isinstance(r, dict)}
    return e


def normalize_multichip(payload: Optional[Dict], source: str,
                        round_: Optional[int]) -> Dict:
    """Two generations of MULTICHIP files: rounds 1-5 are dry-run wrappers
    (``{n_devices, rc, ok, tail}`` — a train step compiled, nothing
    measured), round 6+ are ``bench.py --multichip`` scaling reports whose
    headline is Mrow-tree/s PER CHIP at the max device count plus weak/
    strong scaling efficiency. Both normalize here; only measured entries
    carry a ``value`` and participate in the regression gate."""
    e = {"source": source, "round": round_, "kind": "multichip",
         "ok": None, "n_devices": None, "rc": None,
         "value": None, "unit": None, "platform": None,
         "rows_per_device": None, "tree_learner": None,
         "weak_efficiency": None, "strong_efficiency": None,
         "simulated": None, "error": None}
    if payload:
        for k in ("ok", "n_devices", "rc"):
            if payload.get(k) is not None:
                e[k] = payload[k]
        if payload.get("metric") == "multichip_scaling":
            e["value"] = payload.get("per_chip_mrow_tree_per_s")
            e["unit"] = "Mrow-tree/s/chip"
            for k in ("platform", "rows_per_device", "tree_learner",
                      "weak_efficiency", "strong_efficiency", "simulated",
                      "error"):
                if payload.get(k) is not None:
                    e[k] = payload[k]
    return e


def load_history(root: str) -> List[Dict]:
    """Normalized entries from every checked-in BENCH/MULTICHIP file,
    round order."""
    entries: List[Dict] = []
    # STREAM_r*.json (bench.py --stream) and SERVE_r*.json (bench.py
    # --serve) share the bench schema; the residency=stream / serve=shape
    # fields key each into its own comparability class
    for pat, norm in (("BENCH_r*.json", normalize_bench),
                      ("STREAM_r*.json", normalize_bench),
                      ("SERVE_r*.json", normalize_bench),
                      ("SERVE_CHAOS_r*.json", normalize_bench),
                      ("CHAOS_DIST_r*.json", normalize_bench),
                      ("SPARSE_r*.json", normalize_bench),
                      ("LINEAR_r*.json", normalize_bench),
                      ("INGEST_r*.json", normalize_bench),
                      ("MULTICHIP_r*.json", normalize_multichip)):
        for path in sorted(glob.glob(os.path.join(root, pat))):
            entries.append(norm(payload_of(path), os.path.basename(path),
                                _round_of(path)))
    entries.sort(key=lambda e: (e.get("round") or 0, e["source"]))
    return entries


# ------------------------------------------------------------------ ledger

def _clean(e: Dict) -> bool:
    """A bench entry with a real measurement (nonzero value, no error)."""
    return (e.get("kind") == "bench" and not e.get("error")
            and isinstance(e.get("value"), (int, float)) and e["value"] > 0)


def comparability_key(e: Dict) -> str:
    """Entries are only compared within the same platform, scale, kernel,
    device count, and residency — a 2.1M-row quick pre-bank must never be
    judged against the 10.5M headline, a CPU fallback against a TPU
    number, a deliberate ``LGBM_TPU_BENCH_KERNEL`` A/B arm against a
    different kernel's best, a single-chip headline against an 8-chip
    mesh run, or a host-streamed out-of-core run
    (``tpu_residency=stream``, which pays H2D per wave by design) against
    a fully device-resident one. Serving results (``bench.py --serve``)
    additionally key on the load shape (``serve="closed|b512xc2"``) — a
    1-row-latency arm must never be judged against a 512-row-throughput
    arm, and training benches (serve=None) never mix with serving ones.
    Serve-chaos results (``bench.py --serve-chaos``) key on their
    fault-injection shape (``serve_chaos="open|b4|overload"``): numbers
    measured UNDER injected overload and faults are a comparability class
    of their own. Distributed-chaos results (``bench.py --chaos-dist``,
    CHAOS_DIST_r*.json) key the same way on their gang/fault matrix shape
    (``chaos_dist="gang2|kill9+flap+lease+manifest+shrink"``): fleet MTTR
    and detection latency only compare against runs of the SAME chaos
    matrix. Sparse-bench results (``bench.py --sparse``,
    SPARSE_r*.json) additionally key on the EFB representation
    (``bundle="bundlespace"``): the bundle-space, legacy-unpack, and
    no-EFB arms deliberately trade throughput against memory layout, so a
    sparse arm is never judged cross-representation. Linear-leaf results
    (``bench.py --linear``, LINEAR_r*.json) key on the leaf model
    (``linear="linear"``): a per-leaf ridge-solve workload pays the fit
    leg by design and must never be judged against constant-leaf
    throughput. Ingest results (``bench.py --ingest``, INGEST_r*.json)
    key on the ingest arm (``ingest="device"``): a raw-rows-to-codes
    rows/s number measures the binning pipeline, not training, and never
    mixes with train/serve throughput. Fields absent on older history are
    None — those entries keep comparing among themselves."""
    return (f"platform={e.get('platform')}|rows={e.get('rows')}"
            f"|kernel={e.get('kernel')}|n_devices={e.get('n_devices')}"
            f"|residency={e.get('residency')}|serve={e.get('serve')}"
            f"|serve_chaos={e.get('serve_chaos')}"
            f"|chaos_dist={e.get('chaos_dist')}|bundle={e.get('bundle')}"
            f"|linear={e.get('linear')}|ingest={e.get('ingest')}")


def multichip_key(e: Dict) -> str:
    """Comparability key for measured multichip entries: per-chip numbers
    only compare at the same platform, per-device scale, device count, and
    strategy."""
    return (f"multichip|platform={e.get('platform')}"
            f"|rows_per_device={e.get('rows_per_device')}"
            f"|n_devices={e.get('n_devices')}"
            f"|learner={e.get('tree_learner')}")


def _clean_multichip(e: Dict) -> bool:
    return (e.get("kind") == "multichip" and not e.get("error")
            and isinstance(e.get("value"), (int, float)) and e["value"] > 0)


def best_known_multichip(entries: List[Dict],
                         exclude_source: Optional[str] = None
                         ) -> Dict[str, Dict]:
    """Best measured multichip entry per key (highest per-chip value)."""
    best: Dict[str, Dict] = {}
    for e in entries:
        if not _clean_multichip(e) or e.get("source") == exclude_source:
            continue
        key = multichip_key(e)
        cur = best.get(key)
        if cur is None or e["value"] > cur["value"]:
            best[key] = e
    return best


def best_known(entries: List[Dict],
               exclude_source: Optional[str] = None) -> Dict[str, Dict]:
    """Best clean bench entry per comparability key (highest value; the
    recompile/host-sync/HBM floors are the minima over clean entries of
    the key, carried next to it)."""
    best: Dict[str, Dict] = {}
    for e in entries:
        if not _clean(e) or e.get("source") == exclude_source:
            continue
        key = comparability_key(e)
        cur = best.get(key)
        if cur is None or e["value"] > cur["entry"]["value"]:
            best[key] = {"entry": e}
    for key, slot in best.items():
        group = [e for e in entries if _clean(e)
                 and e.get("source") != exclude_source
                 and comparability_key(e) == key]
        for field in ("recompiles_post_warmup", "host_syncs", "hbm_peak_gb",
                      "p99_ms", "shed_rate", "fleet_mttr_s",
                      "detect_p50_ms", "detect_p99_ms", "shed_epochs"):
            vals = [e[field] for e in group if e.get(field) is not None]
            slot[f"min_{field}"] = min(vals) if vals else None
    return best


def build_ledger(root: str) -> Dict:
    entries = load_history(root)
    best = {k: {"source": v["entry"]["source"],
                "round": v["entry"]["round"],
                "value": v["entry"]["value"],
                "kernel": v["entry"].get("kernel"),
                "min_recompiles_post_warmup":
                    v.get("min_recompiles_post_warmup"),
                "min_host_syncs": v.get("min_host_syncs"),
                "min_hbm_peak_gb": v.get("min_hbm_peak_gb"),
                "min_p99_ms": v.get("min_p99_ms"),
                "min_shed_rate": v.get("min_shed_rate"),
                "min_fleet_mttr_s": v.get("min_fleet_mttr_s"),
                "min_detect_p50_ms": v.get("min_detect_p50_ms"),
                "min_detect_p99_ms": v.get("min_detect_p99_ms"),
                "min_shed_epochs": v.get("min_shed_epochs")}
            for k, v in sorted(best_known(entries).items())}
    best_mc = {k: {"source": v["source"], "round": v["round"],
                   "value": v["value"],
                   "weak_efficiency": v.get("weak_efficiency"),
                   "strong_efficiency": v.get("strong_efficiency")}
               for k, v in sorted(best_known_multichip(entries).items())}
    return {"version": 1,
            "baseline_mrow_tree_per_s": 22.0,
            "entries": entries,
            "best": best,
            "best_multichip": best_mc}


def write_ledger(root: str, out_path: Optional[str] = None,
                 doc: Optional[Dict] = None) -> str:
    from .export import atomic_write_json
    out_path = out_path or os.path.join(root, LEDGER_FILE)
    doc = doc if doc is not None else build_ledger(root)
    return atomic_write_json(out_path, doc, indent=1, sort_keys=True,
                             trailing_newline=True)


def check_ledger(root: str, path: Optional[str] = None) -> bool:
    """True iff the committed ledger matches a fresh rebuild (no drift)."""
    path = path or os.path.join(root, LEDGER_FILE)
    try:
        with open(path) as fh:
            committed = json.load(fh)
    except (OSError, ValueError):
        return False
    return committed == build_ledger(root)


# ----------------------------------------------------------------- compare

def compare(candidate: Dict, entries: List[Dict],
            exclude_source: Optional[str] = None,
            tolerances: Optional[Dict[str, float]] = None
            ) -> Tuple[List[str], List[str]]:
    """Flag regressions of ``candidate`` (a bench payload or normalized
    entry) against the history. Returns (problems, notes): any problem
    means regression — ``bench.py --compare`` exits nonzero on it."""
    tol = dict(DEFAULT_TOLERANCES, **(tolerances or {}))
    problems: List[str] = []
    notes: List[str] = []
    if (candidate.get("kind") == "multichip"
            or candidate.get("metric") == "multichip_scaling"):
        return compare_multichip(candidate, entries,
                                 exclude_source=exclude_source,
                                 tolerances=tolerances)
    c = candidate if candidate.get("kind") == "bench" else \
        normalize_bench(candidate, candidate.get("source", "<candidate>"),
                        candidate.get("round"))
    if not _clean(c):
        problems.append(
            f"candidate has no clean measurement (value={c.get('value')!r}, "
            f"error={c.get('error')!r})")
        return problems, notes
    if c.get("ingest") is not None and c.get("identical_to_host") is False:
        # bit-identity is the ingest contract, not a tolerance band: a
        # faster device binning that changes even one code is a bug
        problems.append(
            "ingest bit-identity violation: device-binned codes differ "
            "from the host oracle (identical_to_host=false)")
    best = best_known(entries, exclude_source=exclude_source)
    key = comparability_key(c)
    slot = best.get(key)
    if slot is None:
        notes.append(f"no comparable history for {key} — nothing to regress "
                     f"against")
    else:
        b = slot["entry"]
        floor = b["value"] * (1.0 - tol["throughput"])
        if c["value"] < floor:
            problems.append(
                f"throughput regression: {c['value']} {c.get('unit') or ''} "
                f"vs best-known {b['value']} ({b['source']}, kernel="
                f"{b.get('kernel')}) — below the {tol['throughput']:.0%} "
                f"band floor {floor:.3g}")
        else:
            notes.append(f"throughput ok: {c['value']} vs best {b['value']} "
                         f"({b['source']})")
        min_rec = slot.get("min_recompiles_post_warmup")
        if (c.get("recompiles_post_warmup") or 0) > 0 and min_rec == 0:
            problems.append(
                f"recompile regression: {c['recompiles_post_warmup']} "
                f"post-warm-up cache miss(es) where history has 0")
        min_sync = slot.get("min_host_syncs")
        if (min_sync is not None and c.get("host_syncs") is not None
                and c["host_syncs"] > min_sync):
            problems.append(
                f"host-sync regression: headline host_syncs "
                f"{c['host_syncs']} vs best-known {min_sync}")
        min_hbm = slot.get("min_hbm_peak_gb")
        if (min_hbm is not None and c.get("hbm_peak_gb") is not None
                and c["hbm_peak_gb"] > min_hbm * (1.0 + tol["hbm"])):
            problems.append(
                f"peak-HBM regression: {c['hbm_peak_gb']} GB vs best-known "
                f"{min_hbm} GB (+{tol['hbm']:.0%} band)")
        min_p99 = slot.get("min_p99_ms")
        if (min_p99 is not None and c.get("p99_ms") is not None
                and c["p99_ms"] > min_p99 * (1.0 + tol["p99"])):
            problems.append(
                f"p99 latency regression: {c['p99_ms']} ms vs best-known "
                f"{min_p99} ms (+{tol['p99']:.0%} band)")
        min_shed = slot.get("min_shed_rate")
        if (min_shed is not None and c.get("shed_rate") is not None
                and c["shed_rate"] > min_shed * (1.0 + tol["shed"]) + 0.05):
            problems.append(
                f"shed-rate regression: {c['shed_rate']} of offered load "
                f"shed vs best-known {min_shed} — shedding more at the "
                f"same offered overload means serving capacity regressed "
                f"(+{tol['shed']:.0%} relative +0.05 absolute band)")
        # chaos-dist recovery gates (bench.py --chaos-dist): wide relative
        # bands plus small absolute allowances, because both numbers ride
        # process relaunch + jit compile wall-clock on a shared box
        min_mttr = slot.get("min_fleet_mttr_s")
        if (min_mttr is not None and c.get("fleet_mttr_s") is not None
                and c["fleet_mttr_s"] > min_mttr * (1.0 + tol["mttr"]) + 5.0):
            problems.append(
                f"fleet-MTTR regression: {c['fleet_mttr_s']} s from gang "
                f"failure to a newer recovery point vs best-known "
                f"{min_mttr} s (+{tol['mttr']:.0%} relative +5s absolute "
                f"band)")
        min_det = slot.get("min_detect_p99_ms")
        if (min_det is not None and c.get("detect_p99_ms") is not None
                and c["detect_p99_ms"]
                > min_det * (1.0 + tol["detect"]) + 200.0):
            problems.append(
                f"peer-loss detection regression: p99 {c['detect_p99_ms']} "
                f"ms to a typed PeerLostError vs best-known {min_det} ms "
                f"(+{tol['detect']:.0%} relative +200ms absolute band)")
        min_se = slot.get("min_shed_epochs")
        if (min_se is not None and c.get("shed_epochs") is not None
                and c["shed_epochs"] > min_se + 1):
            problems.append(
                f"shed-epochs regression: the gang fell back "
                f"{c['shed_epochs']} epoch(s) to agree on a resume point "
                f"vs best-known {min_se} (+1 allowance) — losing more "
                f"banked epochs under the same chaos matrix means the "
                f"manifest commit protocol regressed")
        problems.extend(_cost_drift(c, b, tol["cost"]))
    return problems, notes


def compare_multichip(candidate: Dict, entries: List[Dict],
                      exclude_source: Optional[str] = None,
                      tolerances: Optional[Dict[str, float]] = None
                      ) -> Tuple[List[str], List[str]]:
    """Flag regressions of a ``multichip_scaling`` payload against the
    measured multichip history: per-chip throughput below the tolerance
    band, or scaling efficiency collapsing below best-known minus the band
    — the gate the satellite 'per-chip throughput regressions fail make
    bench-diff' names."""
    tol = dict(DEFAULT_TOLERANCES, **(tolerances or {}))
    problems: List[str] = []
    notes: List[str] = []
    c = candidate if candidate.get("kind") == "multichip" else \
        normalize_multichip(candidate,
                            candidate.get("source", "<candidate>"),
                            candidate.get("round"))
    if not _clean_multichip(c):
        problems.append(
            f"multichip candidate has no clean per-chip measurement "
            f"(value={c.get('value')!r}, error={c.get('error')!r})")
        return problems, notes
    best = best_known_multichip(entries, exclude_source=exclude_source)
    b = best.get(multichip_key(c))
    if b is None:
        notes.append(f"no comparable multichip history for "
                     f"{multichip_key(c)} — nothing to regress against")
        return problems, notes
    floor = b["value"] * (1.0 - tol["throughput"])
    if c["value"] < floor:
        problems.append(
            f"per-chip throughput regression: {c['value']} "
            f"{c.get('unit') or ''} vs best-known {b['value']} "
            f"({b['source']}) — below the {tol['throughput']:.0%} band "
            f"floor {floor:.3g}")
    else:
        notes.append(f"per-chip throughput ok: {c['value']} vs best "
                     f"{b['value']} ({b['source']})")
    for field in ("weak_efficiency", "strong_efficiency"):
        bv, cv = b.get(field), c.get(field)
        # multiplicative band like the throughput check — an absolute
        # delta would never fire for efficiencies below the tolerance
        if bv is not None and cv is not None \
                and cv < bv * (1.0 - tol["throughput"]):
            problems.append(
                f"scaling-efficiency regression: {field} {cv} vs "
                f"best-known {bv} ({b['source']})")
    return problems, notes


def _cost_drift(cand: Dict, base: Dict, rel_tol: float) -> List[str]:
    """Compiled cost-model drift between two entries' shared sites — the
    band logic IS ``costs.drift`` (one implementation; the golden pin and
    the ledger gate cannot disagree on semantics, including 'losing the
    measurement against a recorded number is drift')."""
    from . import costs as _costs
    out: List[str] = []
    cc, bc = cand.get("cost") or {}, base.get("cost") or {}
    for site in sorted(set(cc) & set(bc)):
        bad = _costs.drift(cc[site], bc[site],
                           fields=("flops", "bytes_accessed"),
                           rel_tol=rel_tol)
        for field, info in sorted(bad.items()):
            out.append(
                f"cost drift: {site}.{field} {info['value']} vs recorded "
                f"{info['golden']} ({base['source']}) — ratio "
                f"{info['ratio']} outside +/-{rel_tol:.0%}")
    return out


# --------------------------------------------------------------------- CLI

def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.observability.ledger",
        description="Rebuild/inspect the perf regression ledger "
                    f"({LEDGER_FILE}) from checked-in BENCH_*/MULTICHIP_* "
                    "history")
    ap.add_argument("--root", default=".",
                    help="repo root holding the history files")
    ap.add_argument("--rebuild", action="store_true",
                    help=f"rewrite {LEDGER_FILE} from the history files")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when the committed ledger does not match a "
                         "fresh rebuild (drift)")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    if args.rebuild:
        doc = build_ledger(root)
        path = write_ledger(root, doc=doc)
        print(f"ledger: wrote {path} ({len(doc['entries'])} entries, "
              f"{len(doc['best'])} best-known keys)")
    if args.check:
        if not check_ledger(root):
            print(f"ledger: {LEDGER_FILE} does NOT match the checked-in "
                  f"history — run --rebuild and commit the result")
            return 1
        print("ledger: up to date with history")
    if not args.rebuild and not args.check:
        print(json.dumps(build_ledger(root), indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
