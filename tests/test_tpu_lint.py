"""tpu-lint self-tests: every rule fires on its deliberately-broken fixture
(and ONLY its rule), the clean fixture stays clean, suppressions work at
all three levels (inline pragma, file pragma, baseline), and the live
package lints clean against the committed baseline — the same invocation
`make lint` / the tier-1 verify line runs in CI."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from lightgbm_tpu.analysis.tpu_lint import (Baseline, Finding, lint_file,
                                            lint_paths, main)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
FIXDIR = os.path.join(HERE, "fixtures", "tpu_lint")


@pytest.fixture(autouse=True)
def _isolated_default_cache(tmp_path, monkeypatch):
    """In-process main() calls must not read/write the repo's own
    .tpu_lint_cache.json — debris from a real `make lint` run (different
    rule selections) would leak into the assertions."""
    from lightgbm_tpu.analysis import lint_cache
    monkeypatch.setattr(lint_cache, "DEFAULT_CACHE",
                        str(tmp_path / "lint_cache.json"))

# (fixture path relative to FIXDIR, rule id it must violate)
BAD_FIXTURES = [
    ("bad_r001.py", "R001"),
    (os.path.join("lightgbm_tpu", "ops", "bad_r002.py"), "R002"),
    ("bad_r003.py", "R003"),
    ("bad_r004.py", "R004"),
    ("bad_r005.py", "R005"),
    ("bad_r006.py", "R006"),
    ("bad_r007.py", "R007"),
    (os.path.join("lightgbm_tpu", "bad_r008.py"), "R008"),
    ("bad_r009.py", "R009"),
    (os.path.join("lightgbm_tpu", "bad_r010.py"), "R010"),
    (os.path.join("lightgbm_tpu", "serving", "bad_r011.py"), "R011"),
    (os.path.join("lightgbm_tpu", "bad_r012.py"), "R012"),
    (os.path.join("lightgbm_tpu", "bad_r013.py"), "R013"),
]


@pytest.mark.parametrize("relpath,rule", BAD_FIXTURES)
def test_bad_fixture_violates_exactly_its_rule(relpath, rule):
    findings, err = lint_file(os.path.join(FIXDIR, relpath))
    assert err is None
    assert findings, f"{relpath}: expected {rule} finding(s), got none"
    assert {f.rule for f in findings} == {rule}, \
        f"{relpath}: expected only {rule}, got {[f.format() for f in findings]}"


def test_r007_ignores_sorts_outside_while_loops(tmp_path):
    """Host-side / setup-time sorts are legitimate — R007 only fires on
    code reachable from a lax.while_loop body."""
    p = tmp_path / "mod.py"
    p.write_text("import jax.numpy as jnp\n\n\n"
                 "def host_rank(x):\n"
                 "    return jnp.argsort(x, stable=True)\n")
    findings, err = lint_file(str(p))
    assert err is None and findings == [], [f.format() for f in findings]


def test_r007_grower_compacted_arm_site_is_baseline_exempt():
    """The grower's ONE sort — the compacted arm's slot-grouped row index —
    is the audited site: R007 sees it, the committed baseline absorbs it,
    and nothing else in the wave loop sorts (a sort the TPU's compiler
    hides in a row-sized scatter is counted on the compiled program in
    test_named_scopes.py)."""
    findings, err = lint_file(
        os.path.join(REPO, "lightgbm_tpu", "grower.py"),
        rel=os.path.join("lightgbm_tpu", "grower.py"))
    assert err is None
    r007 = [f for f in findings if f.rule == "R007"]
    assert len(r007) == 1 and "lax.sort" in r007[0].snippet
    bl = Baseline.load(os.path.join(REPO, "tpu_lint_baseline.json"))
    assert bl.suppresses(r007[0])


def test_r008_has_no_exempt_site_left():
    """The TIMETAG accumulator (utils/timer.py) and its two baseline
    entries are gone: no file outside observability/ keeps a perf_counter,
    and the always-on helpers (``timed_span``, ``step_call``) live where
    the rule does not look."""
    bl = json.load(open(os.path.join(REPO, "tpu_lint_baseline.json")))
    assert [f for f in bl["findings"] if f["rule"] == "R008"] == []
    assert not os.path.exists(
        os.path.join(REPO, "lightgbm_tpu", "utils", "timer.py"))
    findings, err = lint_file(
        os.path.join(REPO, "lightgbm_tpu", "observability", "__init__.py"),
        rel=os.path.join("lightgbm_tpu", "observability", "__init__.py"))
    assert err is None
    assert [f for f in findings if f.rule == "R008"] == []


def test_r008_observability_is_exempt():
    """observability/ is the one legitimate home of the timing primitive —
    the tracer/phases modules are full of perf_counter and must stay
    clean."""
    for rel in (("observability", "tracer.py"),
                ("observability", "phases.py"),
                ("observability", "metrics.py")):
        findings, err = lint_file(
            os.path.join(REPO, "lightgbm_tpu", *rel),
            rel=os.path.join("lightgbm_tpu", *rel))
        assert err is None
        assert [f for f in findings if f.rule == "R008"] == [], rel


def test_r009_ignores_transfers_outside_loops(tmp_path):
    """Setup-time device_put (construction placement, residency caches)
    is legitimate — R009 only fires on code reachable from a
    while_loop/scan body."""
    p = tmp_path / "mod.py"
    p.write_text("import jax\nimport numpy as np\n\n\n"
                 "def place(x):\n"
                 "    return jax.device_put(np.asarray(x))\n")
    findings, err = lint_file(str(p))
    assert err is None and findings == [], [f.format() for f in findings]


def test_r010_narrow_and_logged_handlers_are_clean(tmp_path):
    """Only BROAD handlers whose bodies do nothing are flagged: a narrow
    `except OSError: pass` and a broad handler that logs/returns are the
    deliberate patterns the rule points people at."""
    p = tmp_path / "lightgbm_tpu" / "mod.py"
    p.parent.mkdir()
    p.write_text(
        "import os\n\n\n"
        "def a(path):\n"
        "    try:\n"
        "        os.unlink(path)\n"
        "    except OSError:\n"
        "        pass\n\n\n"
        "def b(fn, log):\n"
        "    try:\n"
        "        return fn()\n"
        "    except Exception as e:\n"
        "        log.warning('%s', e)\n"
        "        return None\n")
    findings, err = lint_file(str(p), rel="lightgbm_tpu/mod.py")
    assert err is None
    assert [f for f in findings if f.rule == "R010"] == [], \
        [f.format() for f in findings]


def test_r010_fires_on_broad_silent_handlers(tmp_path):
    p = tmp_path / "lightgbm_tpu" / "mod.py"
    p.parent.mkdir()
    p.write_text(
        "def a(fn):\n"
        "    try:\n"
        "        fn()\n"
        "    except Exception:\n"
        "        pass\n\n\n"
        "def b(items):\n"
        "    for it in items:\n"
        "        try:\n"
        "            it()\n"
        "        except:  # noqa: E722\n"
        "            continue\n")
    findings, err = lint_file(str(p), rel="lightgbm_tpu/mod.py")
    assert err is None
    assert len([f for f in findings if f.rule == "R010"]) == 2


def test_r010_intentional_sites_are_baseline_exempt():
    """The audited silent broad catch — comm.py's jax-private-state
    fallback-of-the-fallback — is seen by R010 and absorbed by the
    committed baseline, and utils/cache.py has none left; the rest of the
    package (incl. robustness/) lints clean, which is the property the
    self-healing layer rides on."""
    bl = Baseline.load(os.path.join(REPO, "tpu_lint_baseline.json"))
    for rel, n in ((("parallel", "comm.py"), 1), (("utils", "cache.py"), 0)):
        findings, err = lint_file(
            os.path.join(REPO, "lightgbm_tpu", *rel),
            rel="/".join(("lightgbm_tpu",) + rel))
        assert err is None
        r010 = [f for f in findings if f.rule == "R010"]
        assert len(r010) == n, [f.format() for f in findings]
        assert all(bl.suppresses(f) for f in r010)


def test_r009_stream_and_dataset_are_exempt():
    """ops/stream.py (the prefetcher — the one sanctioned home of mid-loop
    H2D traffic) and dataset.py (the residency cache) are exempt by
    path."""
    for rel in (("ops", "stream.py"), ("dataset.py",)):
        findings, err = lint_file(
            os.path.join(REPO, "lightgbm_tpu", *rel),
            rel=os.path.join("lightgbm_tpu", *rel))
        assert err is None
        assert [f for f in findings if f.rule == "R009"] == [], rel


def test_r009_fires_on_from_import_alias(tmp_path):
    """`from jax import device_put` must not dodge the rule."""
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent("""\
        import jax
        from jax import device_put
        import numpy as np

        def run(acc):
            def body(c, i):
                return c + device_put(np.zeros(4)).sum(), ()
            out, _ = jax.lax.scan(body, acc, np.arange(3))
            return out
        """))
    findings, err = lint_file(str(p))
    assert err is None
    assert {f.rule for f in findings} == {"R009"}, \
        [f.format() for f in findings]


def test_r011_scoped_to_serving_and_input_normalization_is_clean(tmp_path):
    """R011 only patrols lightgbm_tpu/serving/: the identical sync outside
    that tree is another rule's business, and inside it plain input
    normalization (np.asarray on a caller-provided parameter) stays
    legal — only just-computed (plausibly device) values are flagged."""
    src = ("import numpy as np\n\n\n"
           "def normalize(X):\n"
           "    mat = np.asarray(X, np.float64)\n"
           "    return mat\n\n\n"
           "def batch(parts):\n"
           "    return np.concatenate(parts)\n")
    p = tmp_path / "mod.py"
    p.write_text(src)
    findings, err = lint_file(str(p), rel="lightgbm_tpu/serving/mod.py")
    assert err is None
    assert [f for f in findings if f.rule == "R011"] == [], \
        [f.format() for f in findings]
    # same sync-y code outside serving/ -> out of R011's scope
    bad = ("import numpy as np\n\n\n"
           "def fetch(walk, args):\n"
           "    y = walk(*args)\n"
           "    return np.asarray(y)\n")
    p2 = tmp_path / "mod2.py"
    p2.write_text(bad)
    findings, err = lint_file(str(p2), rel="lightgbm_tpu/ops/mod2.py")
    assert err is None
    assert [f for f in findings if f.rule == "R011"] == []
    findings, err = lint_file(str(p2), rel="lightgbm_tpu/serving/mod2.py")
    assert err is None
    assert len([f for f in findings if f.rule == "R011"]) == 1


def test_r011_contractual_result_sync_is_baseline_exempt():
    """ServingEngine._dispatch's single result fetch — the serving path's
    one contractual device->host sync — is seen by R011 and absorbed by
    the committed baseline; any NEW sync in serving/ fails the lint."""
    findings, err = lint_file(
        os.path.join(REPO, "lightgbm_tpu", "serving", "engine.py"),
        rel="lightgbm_tpu/serving/engine.py")
    assert err is None
    r011 = [f for f in findings if f.rule == "R011"]
    assert len(r011) == 1 and "np.asarray" in r011[0].snippet
    bl = Baseline.load(os.path.join(REPO, "tpu_lint_baseline.json"))
    assert bl.suppresses(r011[0])
    # the batcher and load generators are sync-free by construction
    for mod in ("batcher.py", "loadgen.py", "__init__.py"):
        findings, err = lint_file(
            os.path.join(REPO, "lightgbm_tpu", "serving", mod),
            rel=f"lightgbm_tpu/serving/{mod}")
        assert err is None
        assert [f for f in findings if f.rule == "R011"] == [], mod


def test_r012_daemon_and_joined_threads_are_clean(tmp_path):
    """Either lifecycle discipline passes: daemon=True (dies with the
    process) or a reachable join() in a cleanup method / the same
    function (dies with its owner). The live worker-thread sites —
    batcher worker, serve probe, watchdog monitor, loadgen pools — all
    use one of the two."""
    p = tmp_path / "lightgbm_tpu" / "mod.py"
    p.parent.mkdir()
    p.write_text(
        "import threading\n\n\n"
        "class A:\n"
        "    def __init__(self, work):\n"
        "        self._t = threading.Thread(target=work, daemon=True)\n"
        "        self._t.start()\n\n\n"
        "class B:\n"
        "    def __init__(self, work):\n"
        "        self._t = threading.Thread(target=work)\n"
        "        self._t.start()\n\n"
        "    def close(self):\n"
        "        self._t.join(timeout=5.0)\n\n\n"
        "def fan_out(fns):\n"
        "    ts = [threading.Thread(target=f) for f in fns]\n"
        "    for t in ts:\n"
        "        t.start()\n"
        "    for t in ts:\n"
        "        t.join()\n")
    findings, err = lint_file(str(p), rel="lightgbm_tpu/mod.py")
    assert err is None
    assert [f for f in findings if f.rule == "R012"] == [], \
        [f.format() for f in findings]


def test_r012_fires_without_daemon_or_reachable_join(tmp_path):
    """Non-daemon threads with no join in a cleanup method fire — the
    from-import alias too; a join in a NON-cleanup method does not
    count (it is not reachable on the shutdown path)."""
    p = tmp_path / "lightgbm_tpu" / "mod.py"
    p.parent.mkdir()
    p.write_text(
        "from threading import Thread\n\n\n"
        "class Pool:\n"
        "    def __init__(self, work):\n"
        "        self._t = Thread(target=work)\n"
        "        self._t.start()\n\n"
        "    def maybe_later(self):\n"
        "        self._t.join()\n")
    findings, err = lint_file(str(p), rel="lightgbm_tpu/mod.py")
    assert err is None
    assert len([f for f in findings if f.rule == "R012"]) == 1, \
        [f.format() for f in findings]
    # outside lightgbm_tpu/ -> out of scope (test helpers may leak freely)
    findings, err = lint_file(str(p), rel="tests/helpers/mod.py")
    assert err is None
    assert [f for f in findings if f.rule == "R012"] == []


def test_r012_nested_assign_join_credited_and_str_join_is_not(tmp_path):
    """Two precision pins: (a) a ``self.x = Thread(...)`` nested inside a
    compound statement (if/try) still gets its cleanup join credited —
    no false positive; (b) a ``str.join`` on a local never counts as
    joining a worker, so the fire-and-forget leak next to it still
    fires — no false negative."""
    p = tmp_path / "lightgbm_tpu" / "mod.py"
    p.parent.mkdir()
    p.write_text(
        "import threading\n\n\n"
        "class Guarded:\n"
        "    def __init__(self, work, cond):\n"
        "        self._t = None\n"
        "        if cond:\n"
        "            self._t = threading.Thread(target=work)\n"
        "            self._t.start()\n\n"
        "    def close(self):\n"
        "        if self._t is not None:\n"
        "            self._t.join(timeout=5.0)\n\n\n"
        "def fire(fn, parts):\n"
        "    sep = ','\n"
        "    s = sep.join(parts)\n"
        "    threading.Thread(target=fn).start()\n"
        "    return s\n")
    findings, err = lint_file(str(p), rel="lightgbm_tpu/mod.py")
    assert err is None
    r012 = [f for f in findings if f.rule == "R012"]
    assert len(r012) == 1, [f.format() for f in findings]
    assert r012[0].line > 13, "the Guarded class must be clean"


def test_r012_live_worker_sites_are_clean():
    """The package's real worker threads — micro-batcher worker, serving
    probe, watchdog monitor, chaos killer, loadgen pools — already
    follow the discipline; R012 contributes no baseline entries."""
    for rel in (("serving", "batcher.py"), ("serving", "engine.py"),
                ("serving", "loadgen.py"), ("robustness", "watchdog.py"),
                ("robustness", "chaos.py")):
        findings, err = lint_file(
            os.path.join(REPO, "lightgbm_tpu", *rel),
            rel="/".join(("lightgbm_tpu",) + rel))
        assert err is None
        assert [f for f in findings if f.rule == "R012"] == [], rel


def test_clean_fixture_has_no_findings():
    findings, err = lint_file(os.path.join(FIXDIR, "clean.py"))
    assert err is None
    assert findings == [], [f.format() for f in findings]


def test_allowed_host_sync_waives_r002():
    """The robustness.allowed_host_sync decorator (bare or dotted) marks an
    audited sync point — R002 must skip the function entirely, while the
    undecorated twin fixture in the same hot-path dir still fires."""
    findings, err = lint_file(
        os.path.join(FIXDIR, "lightgbm_tpu", "ops", "waived_r002.py"))
    assert err is None
    assert findings == [], [f.format() for f in findings]


# each CLI arm pays a full interpreter launch (~4 s on the 2-core box);
# tier-1 keeps one representative exit-code arm — per-rule detection is
# covered in-process by test_bad_fixture_violates_exactly_its_rule
@pytest.mark.parametrize("relpath,rule", [
    BAD_FIXTURES[0]] + [pytest.param(*fx, marks=pytest.mark.slow)
                        for fx in BAD_FIXTURES[1:]])
def test_cli_exits_nonzero_on_each_fixture(relpath, rule):
    out = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.analysis",
         os.path.join(FIXDIR, relpath), "--no-baseline"],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 1, out.stdout + out.stderr
    assert rule in out.stdout


def test_live_package_clean_against_committed_baseline():
    out = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.analysis", "lightgbm_tpu/"],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr


def test_inline_pragma_suppresses(tmp_path):
    src = textwrap.dedent("""\
        import jax.numpy as jnp
        BINS = jnp.arange(4)  # tpu-lint: disable=R006
    """)
    p = tmp_path / "mod.py"
    p.write_text(src)
    findings, _ = lint_file(str(p))
    assert findings == []
    # without the pragma the same line fires
    p.write_text(src.replace("  # tpu-lint: disable=R006", ""))
    findings, _ = lint_file(str(p))
    assert [f.rule for f in findings] == ["R006"]


def test_file_pragma_suppresses(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text("# tpu-lint: disable-file=R006\n"
                 "import jax.numpy as jnp\n"
                 "A = jnp.arange(4)\n"
                 "B = jnp.zeros(8)\n")
    findings, _ = lint_file(str(p))
    assert findings == []


def test_baseline_roundtrip_and_consumption(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text("import jax.numpy as jnp\nA = jnp.arange(4)\n")
    findings, _ = lint_file(str(p))
    assert len(findings) == 1
    bl = Baseline.from_findings(findings)
    bl_path = tmp_path / "baseline.json"
    bl.dump(str(bl_path))

    loaded = Baseline.load(str(bl_path))
    assert loaded.suppresses(findings[0])
    # each baseline entry suppresses exactly its count — a SECOND identical
    # finding (a regression on another line) still fails
    dup = Finding(**{**findings[0].__dict__, "line": 99})
    assert not loaded.suppresses(dup)


def test_baseline_fingerprint_survives_line_moves(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text("import jax.numpy as jnp\nA = jnp.arange(4)\n")
    findings, _ = lint_file(str(p))
    bl = Baseline.from_findings(findings)
    # unrelated edit above shifts the line; fingerprint (file, rule,
    # snippet) still matches
    p.write_text("import jax.numpy as jnp\n\n\nA = jnp.arange(4)\n")
    moved, _ = lint_file(str(p))
    assert len(moved) == 1 and moved[0].line != findings[0].line
    assert bl.suppresses(moved[0])


def test_main_select_and_json_format(capsys):
    rc = main([os.path.join(FIXDIR, "bad_r001.py"), "--no-baseline",
               "--select", "R004", "--format", "json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 0 and data["findings"] == []   # R001 file, R004-only scan
    rc = main([os.path.join(FIXDIR, "bad_r001.py"), "--no-baseline",
               "--select", "R001", "--format", "json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 1 and [f["rule"] for f in data["findings"]] == ["R001"]


def test_syntax_error_reported_not_crash(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    findings, errors = lint_paths([str(p)])
    assert findings == []
    assert len(errors) == 1 and "cannot parse" in errors[0]


# ------------------------------------------------ whole-package call graph

XMOD = os.path.join(FIXDIR, "xmod")


def test_r007_cross_module_reach():
    """The argsort lives in helpers_r007.py, the while_loop in
    loops_r007.py — only the package call graph connects them. The
    identical sort NOT reachable from a loop stays clean."""
    findings, errors = lint_paths([XMOD])
    assert errors == []
    r007 = [f for f in findings if f.rule == "R007"]
    assert len(r007) == 1, [f.format() for f in findings]
    assert r007[0].path.endswith("helpers_r007.py")
    assert "regroup" in r007[0].message


def test_r009_cross_module_reach():
    findings, _ = lint_paths([XMOD])
    r009 = [f for f in findings if f.rule == "R009"]
    assert len(r009) == 1, [f.format() for f in findings]
    assert r009[0].path.endswith("helpers_r009.py")


def test_r012_cross_module_join_delegation():
    """Delegated.close() hands self._worker to helpers_r012.stop_thread,
    which joins its parameter — credited through the call graph, clean.
    Leaky delegates to a helper that never joins — still fires."""
    findings, _ = lint_paths([XMOD])
    r012 = [f for f in findings if f.rule == "R012"]
    assert len(r012) == 1, [f.format() for f in findings]
    assert r012[0].path.endswith("workers_r012.py")
    # the one finding is Leaky's thread, not Delegated's
    src = open(os.path.join(XMOD, "lightgbm_tpu", "workers_r012.py")).read()
    leaky_at = src[:src.index("class Leaky")].count("\n") + 1
    assert r012[0].line > leaky_at


def test_cross_module_rules_need_package_context():
    """Standalone single-file lint (same-file semantics) cannot see the
    loop in the other module — the helper lints clean alone, which is
    exactly why lint_paths builds the package index."""
    findings, err = lint_file(os.path.join(XMOD, "helpers_r007.py"))
    assert err is None and findings == []


# ---------------------------------------------------------- incremental cache

def test_cache_replays_without_reparsing(tmp_path):
    from unittest import mock

    from lightgbm_tpu.analysis.lint_cache import LintCache

    p = tmp_path / "mod.py"
    p.write_text("import jax.numpy as jnp\nA = jnp.arange(4)\n")
    cache_path = str(tmp_path / "cache.json")
    first, _ = lint_paths([str(p)], cache=LintCache(cache_path))
    assert len(first) == 1

    with mock.patch("lightgbm_tpu.analysis.tpu_lint._parse_source",
                    side_effect=AssertionError("cache miss re-parsed")):
        replayed, _ = lint_paths([str(p)], cache=LintCache(cache_path))
    assert [f.__dict__ for f in replayed] == [f.__dict__ for f in first]


def test_cache_invalidated_by_content_and_rule_changes(tmp_path):
    from lightgbm_tpu.analysis.lint_cache import LintCache

    p = tmp_path / "mod.py"
    p.write_text("import jax.numpy as jnp\nA = jnp.arange(4)\n")
    cache_path = str(tmp_path / "cache.json")
    lint_paths([str(p)], cache=LintCache(cache_path))

    # content change: full pipeline runs again, new finding appears
    p.write_text("import jax.numpy as jnp\nA = jnp.arange(4)\n"
                 "B = jnp.zeros(8)\n")
    findings, _ = lint_paths([str(p)], cache=LintCache(cache_path))
    assert len(findings) == 2

    # rule-list change: fingerprint matches but the rule ids don't — the
    # cached replay must refuse
    cache = LintCache(cache_path)
    sources = [(str(p), os.path.relpath(str(p)).replace(os.sep, "/"),
                p.read_text())]
    assert cache.replay(sources, ["R006"]) is None


# --------------------------------------------- stale baseline + update CLI

def test_stale_baseline_entry_fails_lint(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text("import jax.numpy as jnp\nA = jnp.arange(4)\n")
    base = tmp_path / "base.json"
    rc = main([str(p), "--no-cache", "--baseline", str(base),
               "--update-baseline"])
    assert rc == 0 and base.exists()
    rc = main([str(p), "--no-cache", "--baseline", str(base)])
    assert rc == 0

    # fix the finding: the baseline entry now matches nothing -> stale
    p.write_text("import jax.numpy as jnp\n\n\ndef f(x):\n"
                 "    return jnp.arange(4)\n")
    rc = main([str(p), "--no-cache", "--baseline", str(base)])
    assert rc == 1

    # --update-baseline clears the stale entry
    rc = main([str(p), "--no-cache", "--baseline", str(base),
               "--update-baseline"])
    assert rc == 0
    rc = main([str(p), "--no-cache", "--baseline", str(base)])
    assert rc == 0


def test_stale_entries_ignored_for_unlinted_files(tmp_path):
    """A subset-path run proves nothing about files it did not lint —
    their baseline entries must not be reported stale."""
    from lightgbm_tpu.analysis.tpu_lint import stale_baseline_entries

    a = tmp_path / "a.py"
    a.write_text("import jax.numpy as jnp\nA = jnp.arange(4)\n")
    findings, _ = lint_paths([str(a)])
    bl = Baseline.from_findings(findings)
    # 'a.py' entry unconsumed, but a.py was NOT in this (empty) run and
    # still exists on disk -> not stale
    rel = findings[0].path
    assert stale_baseline_entries(bl, linted_rels=set()) == []
    # linted this run without consuming the entry -> stale
    assert [k for k, _ in stale_baseline_entries(bl, {rel})] == [
        (rel, findings[0].rule, findings[0].snippet)]
