"""Every phase of the step and of the resident wave body sits in a
``jax.named_scope`` (docs/Observability.md, "Scopes"): the lowered step
carries each name in its ``op_name`` locations, and the operations of the
step COMPILED for a described v5e — the fusions a device trace times —
carry them in their ``metadata``. Metadata only: no generated code moves.

The one test file that describes a TPU topology (in a fixture, skipped
where it cannot be described; on-chip-measurement guide, section 2).
"""
import os
import re

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb

STEP_SCOPES = ("step.gradients", "step.sampling", "step.grow",
               "step.score_update", "step.valid_update")
TREE_SCOPES = ("tree.pack_rows", "tree.root_sums")
WAVE_SCOPES = ("wave.slots", "wave.hist.stream", "wave.hist.compact",
               "wave.hist.compact.gather", "wave.hist.reduce", "wave.split",
               "wave.route", "wave.partition", "wave.stats")
KERNEL_SCOPES = ("hist.kernel",)
ALL_SCOPES = STEP_SCOPES + TREE_SCOPES + WAVE_SCOPES + KERNEL_SCOPES

PARAMS = dict(objective="binary", num_leaves=15, max_bin=31, verbose=-1,
              min_data_in_leaf=5, metric="binary_logloss", device="cpu",
              bagging_fraction=0.7, bagging_freq=1, tpu_compact_frac=0.5)


def _step_and_args(**extra):
    """A small booster's jitted step and the arguments ``_run_step`` would
    hand it: bagging and a validation set, so every phase has operations."""
    rng = np.random.RandomState(2)
    X = rng.rand(2500, 8).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0.8).astype(np.float32)
    params = dict(PARAMS, **extra)
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    bst.add_valid(lgb.Dataset(X[:300], label=y[:300], reference=ds), "v")
    g = bst._gbdt
    consts, valid_Xb, valid_scores = g._dispatch_prep(g._step_shrinkage())
    args = (consts, valid_Xb, g.score, valid_scores, g.bag_mask, g._rng_key,
            g._iter_dev, g._shrink_cache[1])
    return g._make_step(), args


def _named(text: str, scope: str) -> int:
    """Operations whose scope path has ``scope`` as one whole component."""
    return len(re.findall(r'[/"]' + re.escape(scope) + r'[/"]', text))


@pytest.mark.parametrize("learner", ["serial", "data"])
def test_lowered_step_names_every_phase(learner):
    fn, args = _step_and_args(tree_learner=learner)
    text = fn.lower(*args).as_text(debug_info=True)
    missing = [s for s in ALL_SCOPES if not _named(text, s)]
    # serial growth reduces nothing: comm.reduce_hist is the identity
    if learner == "serial":
        assert missing == ["wave.hist.reduce"]
    else:
        assert missing == []
    if learner == "serial":
        # the wave phases are inside the loop inside the grow phase
        assert re.search(r'step\.grow/[^"]*while/body/[^"]*wave\.route/',
                         text)
        assert re.search(r'wave\.hist\.compact/[^"]*hist\.kernel/[^"]*'
                         r'wave\.hist\.compact\.gather/', text)


def _row_sized(hlo: str, rows: int, op: str):
    """The compiled program's ``op`` instructions over a row-sized array."""
    return [ln for ln in hlo.splitlines()
            if f" {op}(" in ln and f"[{rows}]" in ln]


_IN_COMPACT_ARM = re.compile(r'op_name="[^"]*/while/body/[^"]*cond/[^"]*'
                             r'wave\.hist\.compact/wave\.partition/')


def test_row_sized_sorts_of_the_compiled_step():
    """The step as compiled: exactly ONE row-sized sort, under
    ``wave.partition`` inside the compacted arm of the wave's ``cond``, and
    no row-sized reduce-window (a cumsum) or scatter anywhere: those are
    what the TPU's compiler turns into a sort and reduce-windows in every
    wave (the v5e case below)."""
    fn, args = _step_and_args()
    hlo = fn.lower(*args).compile().as_text()
    sorts = _row_sized(hlo, 2560, "sort")            # 2500 rows, padded
    assert len(sorts) == 1 and _IN_COMPACT_ARM.search(sorts[0]), sorts
    assert not _row_sized(hlo, 2560, "reduce-window")
    assert not _row_sized(hlo, 2560, "scatter")


def test_streamed_legs_share_the_split_and_route_scopes():
    """``_apply_wave_splits`` and ``_route_rows`` carry their scope inside,
    so the host-driven streamed grower's legs are named like the resident
    loop's."""
    from lightgbm_tpu.grower import _route_rows, empty_route_table
    from lightgbm_tpu.analysis.contracts.entries import _wave_spec
    import jax.numpy as jnp
    spec = _wave_spec()
    route = jax.jit(lambda X, lid, table: _route_rows(
        X, lid, table, None, spec, None, jnp.zeros(6, jnp.int32)))
    text = route.lower(jnp.zeros((64, 6), jnp.uint8), jnp.zeros(64, jnp.int32),
                       empty_route_table(spec, None)
                       ).as_text(debug_info=True)
    assert _named(text, "wave.route")


# --------------------------------------- compiled for a described v5e chip

@pytest.fixture(scope="module")
def v5e_chips():
    """The four chips of a described v5e 2x2 host."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — whatever keeps it away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return list(topo.devices)


@pytest.fixture(scope="module")
def one_chip(v5e_chips):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_chips[0])


def _compiled_text(lowered) -> str:
    """``lowered`` compiled (for a described chip), as text. Such a program
    cannot be read back from the persistent cache: keep it out, and the run
    silent."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def v5e_hlo(one_chip):
    """The default step compiled for the described chip, as text."""
    fn, args = _step_and_args()
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=one_chip), args)
    return _compiled_text(fn.lower(*shapes))


def test_v5e_fusions_carry_the_scopes(v5e_hlo):
    """What the device trace times are the compiled program's fusions:
    each phase that moves rows owns at least one, named in its metadata."""
    ops = [ln for ln in v5e_hlo.splitlines() if "metadata={" in ln]
    fusions = [ln for ln in ops if " fusion(" in ln]
    assert len(fusions) > 50
    owns_fusions = ("step.gradients", "step.grow",
                    "step.score_update", "step.valid_update",
                    "tree.pack_rows", "wave.slots", "wave.hist.stream",
                    "wave.hist.compact", "wave.hist.compact.gather",
                    "wave.split", "wave.route", "wave.partition",
                    "hist.kernel")
    for scope in owns_fusions:
        assert any(re.search(r'op_name="[^"]*[/]' + re.escape(scope)
                             + r'[/"]', ln) for ln in fusions), scope
    # bagging's draw is a generator call whose compare fuses downstream
    assert any("/step.sampling/" in ln for ln in ops)
    # the matmul the histogram IS sits under hist.kernel, in both arms
    dots = [ln for ln in ops if re.search(r"kind=kOutput|convolution\(", ln)
            and "hist.kernel" in ln]
    assert any("wave.hist.stream" in ln for ln in dots)
    assert any("wave.hist.compact" in ln for ln in dots)


def test_v5e_step_sorts_rows_once_in_the_compacted_arm(v5e_hlo):
    """What no jaxpr walk can see: the sorts the TPU's compiler itself puts
    in (it expands a row-sized scatter into a sort of (index, value) pairs:
    the carried partition, deleted in PR 32, paid one in every wave,
    PERF.md PR 27). The step compiled for the v5e holds exactly one row-sized sort, the
    compacted arm's own, and no row-sized reduce-window or scatter."""
    sorts = _row_sized(v5e_hlo, 2560, "sort")
    assert len(sorts) == 1 and _IN_COMPACT_ARM.search(sorts[0]), sorts
    assert not _row_sized(v5e_hlo, 2560, "reduce-window")
    assert not _row_sized(v5e_hlo, 2560, "scatter")


# ---- the one-leaf form of the chunk matmul (ops/histogram.one_leaf_form) ----

def _with_the_form(monkeypatch, on: bool):
    """The one-leaf kernel's device switch, as a test steers it: the booster
    is built on the CPU, where the Mosaic kernel does not exist."""
    from lightgbm_tpu.ops import pallas_histogram
    monkeypatch.setattr(pallas_histogram, "one_leaf_runs_on",
                        lambda platform: on)


@pytest.fixture(scope="module")
def v5e_hlo_one_leaf(one_chip):
    """The default step WITH the one-leaf form, compiled for the described
    chip, as text."""
    mp = pytest.MonkeyPatch()
    try:
        _with_the_form(mp, True)
        fn, args = _step_and_args(tpu_hist_chunk=512)
    finally:
        mp.undo()
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=one_chip), args)
    return _compiled_text(fn.lower(*shapes))


def test_v5e_hist_kernel_encloses_the_one_leaf_matmul_in_both_arms(
        v5e_hlo_one_leaf):
    """The one-leaf form's matmul is a Mosaic kernel (``hist_one_leaf``): in
    the step compiled for the chip it sits under ``hist.kernel`` in the
    streamed and in the compacted arm of the wave's switch, beside the
    general form's fusions, which are still there."""
    ops = [ln for ln in v5e_hlo_one_leaf.splitlines() if "metadata={" in ln]
    kernels = [ln for ln in ops if "tpu_custom_call" in ln
               and "hist_one_leaf" in ln]
    assert kernels and all("hist.kernel" in ln for ln in kernels)
    assert any("wave.hist.stream" in ln for ln in kernels)
    assert any("wave.hist.compact" in ln for ln in kernels)
    dots = [ln for ln in ops if re.search(r"kind=kOutput|convolution\(", ln)
            and "hist.kernel" in ln]
    assert any("wave.hist.stream" in ln for ln in dots)
    assert any("wave.hist.compact" in ln for ln in dots)
    # each compacted arm sorts the rows once, and no other row-sized sort
    sorts = _row_sized(v5e_hlo_one_leaf, 2560, "sort")
    assert len(sorts) == 2 and all(
        "wave.hist.compact/wave.partition" in ln for ln in sorts), sorts


@pytest.mark.parametrize("extra", [
    dict(tpu_hist_f64=True), dict(tpu_hist_kernel="pallas"),
    dict(tpu_row_compact=False, tpu_hist_f64=True)],
    ids=["f64", "pallas", "f64-no-row-compact"])
def test_other_weight_modes_and_kernels_lower_to_the_same_text(
        monkeypatch, extra):
    """Paths that keep the general form by construction: their lowered step
    is the same text with the one-leaf kernel's device there or not (the
    parent's text: PERF.md, PR 37, compared by hand)."""
    from lightgbm_tpu.ops import pallas_histogram
    monkeypatch.setattr(pallas_histogram, "_INTERPRET",
                        extra.get("tpu_hist_kernel") == "pallas")
    texts = []
    for on in (False, True):
        _with_the_form(monkeypatch, on)
        fn, args = _step_and_args(**extra)
        texts.append(fn.lower(*args).as_text())
    assert texts[0] == texts[1] and "hist_one_leaf" not in texts[0]


def test_the_streamed_growers_shard_leg_lowers_to_the_same_text(monkeypatch):
    """``StreamedGrower.shard_body`` threads the general form's accumulator
    through its legs (``acc_init`` / ``raw_output``): the one-leaf form is
    not there, whatever the device."""
    texts = []
    for on in (False, True):
        _with_the_form(monkeypatch, on)
        rng = np.random.RandomState(2)
        X = rng.rand(2500, 8).astype(np.float32)
        y = (X[:, 0] + X[:, 1] * X[:, 2] > 0.8).astype(np.float32)
        params = dict(PARAMS, bagging_fraction=1.0, bagging_freq=0,
                      tpu_residency="stream", tpu_stream_shard_rows=256)
        bst = lgb.Booster(params=params,
                          train_set=lgb.Dataset(X, label=y, params=params))
        g = bst._gbdt
        assert g.spec.one_leaf_frac == 0
        grower, leg = g._streamed_grower, {}
        jitted = grower.shard_fn

        def spy(*a, **kw):
            leg.setdefault("text", jitted.lower(*a, **kw).as_text())
            return jitted(*a, **kw)
        grower.shard_fn = spy
        bst.update()
        texts.append(leg["text"])
    assert texts[0] == texts[1] and "hist_one_leaf" not in texts[0]


def test_the_default_step_takes_the_form_where_its_kernel_exists(monkeypatch):
    """The same booster with the kernel's device there: the wave's ``cond``
    becomes a four-armed switch and the loop carries ``one_leaf``."""
    _with_the_form(monkeypatch, False)
    fn, args = _step_and_args()
    without = str(fn.trace(*args).jaxpr)
    _with_the_form(monkeypatch, True)
    fn, args = _step_and_args()
    jaxpr = str(fn.trace(*args).jaxpr)
    assert "hist_one_leaf" in jaxpr and "hist_one_leaf" not in without
    # one call in the streamed arm and one in the compacted arm
    assert jaxpr.count("name=_one_leaf_call") == 2
    assert "pallas_call" in jaxpr and "pallas_call" not in without


def _route_widths(hlo: str, rows: int):
    """Minor widths ``w`` of the row-sized 2-D values ``[rows, w]`` the
    compiled program computes under ``wave.route``: a one-hot over T keys is
    a compare of that width there, whatever consumes it."""
    widths = set()
    for ln in hlo.splitlines():
        if not re.search(r'op_name="[^"]*/wave\.route/', ln):
            continue
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[(\d+),(\d+)\]", ln)
        if m and int(m.group(1)) == rows:
            widths.add(int(m.group(2)))
    return widths


def test_v5e_routing_matches_no_wider_than_the_waves_splits(v5e_hlo, one_chip):
    """Routing resolves a row against the wave's ``hist_slots`` split
    leaves, never against all ``L + 1`` (PERF.md, PR 33: the 256-wide
    one-hot lookup was two thirds of ``wave.route``). In the step compiled
    for the v5e nothing row-sized under ``wave.route`` is wider than the
    slots (14 here; the feature one-hot and the table's columns are 8), and
    no value there has an ``L + 1`` = 16 axis at all; the same reading
    flags the old form, ``table_lookup`` over a ``[L + 1, 6]`` table."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import table_lookup
    slots, leaves = 14, 15            # PARAMS: min(25, num_leaves - 1)
    widths = _route_widths(v5e_hlo, 2560)
    assert slots in widths and max(widths) == slots, widths
    route_shapes = re.findall(
        r'= \w+\[([\d,]+)\][^\n]*op_name="[^"]*/wave\.route/', v5e_hlo)
    assert route_shapes
    assert not [s for s in route_shapes
                if str(leaves + 1) in s.split(",")], route_shapes
    # the control: what the reading says of the lookup this PR removed
    old = jax.jit(jax.named_scope("wave.route")(table_lookup)).lower(
        jax.ShapeDtypeStruct((2560,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((leaves + 1, 6), jnp.int32, sharding=one_chip))
    assert leaves + 1 in _route_widths(_compiled_text(old), 2560)


# ------------------------- the data-parallel step, for four described chips

# rows a device. The cell runs 11,010,048; the step compiles in a quarter of
# a minute at this size and in three at that one (by hand: PERF.md, PR 34),
# and the partitioner treats a row-sized value alike at both
DP_ROWS = 1_048_576
COLLECTIVE_SCOPES = ("tree.root_sums", "wave.hist.reduce", "wave.split.allgather")
COLLECTIVE_PRIMITIVES = ("psum", "psum2", "psum_invariant", "pmax", "pmin",
                         "reduce_scatter", "all_gather", "all_gather_invariant",
                         "ppermute", "all_to_all")
COLLECTIVE_OPCODES = ("all-reduce", "reduce-scatter", "all-gather",
                      "collective-permute", "all-to-all")


@pytest.fixture(scope="module")
def dp_step(v5e_chips):
    """``tree_learner=data`` over four devices, as the benchmark's four-chip
    cell runs it: (the step traced, as a jaxpr; the step compiled for the four
    described chips at ``DP_ROWS`` rows a device, as text)."""
    return _dp_step(v5e_chips)


@pytest.fixture(scope="module")
def dp_step_blocked_tail(v5e_chips):
    """The same step with the wave's tail in blocks of three slot pairs, as a
    wide table runs it (``grower.scan_block_pairs``: the constant is moved to
    put this 16-column table on the wide side)."""
    from lightgbm_tpu import grower
    was = grower._SCAN_BLOCK_BYTES
    grower._SCAN_BLOCK_BYTES = 3 * 2 * 4 * 256 * 12      # a shard scans 4 columns
    try:
        return _dp_step(v5e_chips)
    finally:
        grower._SCAN_BLOCK_BYTES = was


def _dp_step(v5e_chips):
    from jax.sharding import NamedSharding
    from lightgbm_tpu.parallel.comm import ParallelContext
    rng = np.random.RandomState(3)
    X = rng.rand(4 * 4096, 16).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0.8).astype(np.float32)
    params = dict(PARAMS, max_bin=255, tree_learner="data", num_machines=4,
                  bagging_fraction=1.0, bagging_freq=0)
    bst = lgb.Booster(params=params,
                      train_set=lgb.Dataset(X, label=y, params=params))
    g = bst._gbdt
    consts, valid_Xb, valid_scores = g._dispatch_prep(g._step_shrinkage())
    args = (consts, valid_Xb, g.score, valid_scores, g.bag_mask, g._rng_key,
            g._iter_dev, g._shrink_cache[1])
    # the same booster over the described chips: the step closes over the mesh
    g.pctx = ParallelContext("data", v5e_chips)
    small = int(g.num_data_padded)

    def described(x):
        shape = tuple(4 * DP_ROWS if d == small else d for d in x.shape)
        return jax.ShapeDtypeStruct(
            shape, x.dtype, sharding=NamedSharding(g.pctx.mesh, x.sharding.spec))

    traced = g._make_step().trace(*jax.tree.map(described, args))
    return traced.jaxpr, _compiled_text(traced.lower())


def _walk(jaxpr, inside_cond=False):
    """(equation, whether a ``cond`` encloses it) over a jaxpr and every
    jaxpr its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_cond
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list)) else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub, inside_cond
                                     or eqn.primitive.name == "cond")


def test_dp_every_collective_sits_under_a_named_scope(dp_step):
    """A device trace files collective time under a scope: the root's psum,
    the histograms' reduce-scatter, the candidates' all-gather. None of them
    inside an arm of the wave's ``cond``: shards may take different arms."""
    jaxpr, _ = dp_step
    found = [(eqn.primitive.name, str(eqn.source_info.name_stack), in_cond)
             for eqn, in_cond in _walk(jaxpr.jaxpr)
             if eqn.primitive.name in COLLECTIVE_PRIMITIVES]
    assert found
    for scope in COLLECTIVE_SCOPES:
        assert any(scope in stack.split("/") for _, stack, _ in found), scope
    for name, stack, in_cond in found:
        # past 2^24 rows a table also sums its leaves' counts as integers
        assert set(COLLECTIVE_SCOPES + ("tree.exact_counts",)) \
            & set(stack.split("/")), (name, stack)
        assert not in_cond, (name, stack)
    conds = [eqn for eqn, _ in _walk(jaxpr.jaxpr) if eqn.primitive.name == "cond"]
    assert any("wave.hist.compact" in str(e) for eqn in conds
               for e, _ in _walk(eqn.params["branches"][1].jaxpr)
               for e in [e.source_info.name_stack])


def _computations(hlo: str) -> dict:
    """{computation name: its lines} of a compiled module's text."""
    comps, name = {}, None
    for ln in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", ln)
        if head and not ln.startswith(" "):
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(ln)
    return comps


def _collective_lines(lines):
    return [ln for ln in lines if re.search(
        r"= [^=]*?\b(?:" + "|".join(COLLECTIVE_OPCODES) + r")(?:-start|-done)?\(", ln)]


def test_dp_compiled_step_exchanges_nothing_row_sized(dp_step):
    """The step compiled for four v5e chips: what crosses devices is
    histograms, candidates and scalars, never a value with a row's worth of
    elements (the score update looked leaf values up through the partitioner
    and all-gathered the leaf ids until PR 34); the compacted arm of the
    wave's ``cond`` and the streamed one hold no collective; one row-sized
    sort a wave a shard, the compacted arm's own."""
    _, hlo = dp_step
    collectives = _collective_lines(hlo.splitlines())
    assert len(collectives) >= 3
    for ln in collectives:
        result = ln.split(" = ", 1)[1].split("(%")[0]
        sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
                 for dims in re.findall(r"\w+\[([\d,]*)\]", result)]
        assert max(sizes) < DP_ROWS // 4, ln[:200]
    # the wave's cond: neither arm, nor anything an arm calls
    comps = _computations(hlo)
    waves = [ln for lines in comps.values() for ln in lines
             if " conditional(" in ln and "/while/body/cond" in ln]
    assert len(waves) == 1, waves
    todo = re.findall(r"%([\w.\-]+)", waves[0].split("branch_computations={")[1]
                      .split("}")[0])
    assert len(todo) == 2
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        assert not _collective_lines(comps[name]), name
        for ln in comps[name]:
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", ln)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", ln):
                todo += re.findall(r"%([\w.\-]+)", group)
    assert len(seen) > 2
    sorts = _row_sized(hlo, DP_ROWS, "sort")
    assert len(sorts) == 1 and _IN_COMPACT_ARM.search(sorts[0]), sorts
    assert not _row_sized(hlo, DP_ROWS, "scatter")


def test_dp_blocked_tail_holds_no_collective(dp_step_blocked_tail):
    """The wave's tail in blocks (a wide table): ONE loop under ``wave.split``
    whose body is the cache's write-back and the scan of a few slot pairs, and
    nothing that crosses devices inside it. The reduce-scatter of all the
    wave's histograms and the candidates' all-gather keep their static shape
    outside, so a shard whose wave holds fewer leaves waits for nobody in the
    middle of a collective."""
    jaxpr, hlo = dp_step_blocked_tail

    def loops(jaxpr, under=()):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "while":
                yield eqn, under
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (tuple, list)) else (value,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from loops(sub, under + (eqn,))

    tails = [eqn for eqn, under in loops(jaxpr.jaxpr)
             if "wave.split" in str(eqn.source_info.name_stack).split("/")
             and any(e.primitive.name == "while" for e in under)]
    assert len(tails) == 1, [str(e.source_info.name_stack) for e in tails]
    inside = [e.primitive.name for e, _ in _walk(tails[0].params["body_jaxpr"].jaxpr)]
    assert "scatter" in inside and "cumsum" in inside      # write-back, scan
    assert not set(inside) & set(COLLECTIVE_PRIMITIVES)
    # the collectives are all still there, outside it
    found = [str(eqn.source_info.name_stack) for eqn, _ in _walk(jaxpr.jaxpr)
             if eqn.primitive.name in COLLECTIVE_PRIMITIVES]
    for scope in COLLECTIVE_SCOPES:
        assert any(scope in stack.split("/") for stack in found), scope

    # and in the program compiled for the four chips: the tail's loop, and
    # every computation it calls
    comps = _computations(hlo)
    heads = [ln for lines in comps.values() for ln in lines
             if " while(" in ln
             and re.search(r'op_name="[^"]*/while/body/wave\.split/while"', ln)]
    assert len(heads) == 1, heads
    todo = re.findall(r"(?:body|condition)=%([\w.\-]+)", heads[0])
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        assert not _collective_lines(comps[name]), name
        for ln in comps[name]:
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", ln)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", ln):
                todo += re.findall(r"%([\w.\-]+)", group)
    assert len(seen) > 2
    assert len(_collective_lines(hlo.splitlines())) >= 3
