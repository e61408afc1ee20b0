"""Program builders + the shipped contract set for the trace tier.

Importing this module pulls in jax and the product modules (which
registers their ``@trace_entry`` hooks), defines one builder per
(entry, shape_class) cell of the matrix, and registers contracts
T001-T010. Builders trace/compile against the SHIPPED callables fetched
through :func:`get_entry` — never a local copy — so a refactor that
breaks an entry point fails here, loudly, instead of silently pinning
dead code.

Shape classes:

- ``serial``        single-device resident growth / fused train step
- ``u4_packed``     u4 packed-row code layout (tpu_code_mode=u4)
- ``data8``         data-parallel over the 8 hermetic CPU devices
- ``stream_shard``/``stream_wave``  StreamedGrower's two device legs
- ``bundled``       native EFB bundle-space routing
- ``bundled_unpack`` tpu_efb_unpack=true legacy decode arm (violates)
- ``linear``        linear_tree=true ridge-fit legs
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

# product-module imports populate ENTRY_POINTS via @trace_entry
import lightgbm_tpu.boosting.gbdt   # noqa: F401
import lightgbm_tpu.grower          # noqa: F401
import lightgbm_tpu.ops.linear      # noqa: F401
import lightgbm_tpu.ops.predict    # noqa: F401

from . import checks as C
from .registry import (Target, TracedProgram, contract, get_entry,
                       program_builder)


# --------------------------------------------------------- grower.wave_body

def _wave_spec(**over):
    from lightgbm_tpu.grower import GrowerSpec
    kw = dict(num_leaves=15, num_features=6, num_bins_padded=16,
              chunk_rows=256, hist_slots=4, wave_size=4, max_depth=0,
              lambda_l1=0.0, lambda_l2=0.0, min_data_in_leaf=5.0,
              min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
              row_compact=True, compact_frac=1.0)
    kw.update(over)
    return GrowerSpec(**kw)


def _wave_program(shape_class: str, spec, comm=None, comm_bytes=None,
                  N: int = 1024, grow=None, rows: int = 0) -> TracedProgram:
    F, B = spec.num_features, spec.num_bins_padded
    if grow is None:
        entry = get_entry("grower.wave_body")

        def grow(X, g, h, inc, fok, iscat, nb, mc, db):
            return entry(X, g, h, inc, fok, iscat, nb, mc, db, spec, comm)
    rng = np.random.RandomState(0)
    X = jnp.asarray(rng.randint(0, B, size=(N, F)).astype(np.uint8))
    g = jnp.asarray(rng.randn(N).astype(np.float32))
    ones = jnp.ones(N, jnp.float32)
    nb = jnp.full(F, B, jnp.int32)
    zf = jnp.zeros(F, jnp.int32)
    jx = jax.make_jaxpr(
        lambda Xa, gg, hh, inc: grow(Xa, gg, hh, inc, jnp.ones(F, bool),
                                     jnp.zeros(F, bool), nb, zf, zf))(
        X, g, ones, ones)
    return TracedProgram("grower.wave_body", shape_class, jx, comm=comm_bytes,
                         rows=rows or N)


@program_builder("grower.wave_body", "serial")
def _wave_serial():
    return _wave_program("serial", _wave_spec())


@program_builder("grower.wave_body", "u4_packed")
def _wave_u4():
    # u4 packed-row layout: 16 bins fit a nibble, histogram build unpacks
    return _wave_program("u4_packed", _wave_spec(code_mode="u4"))


@program_builder("grower.wave_body", "data8")
def _wave_data8():
    from lightgbm_tpu.parallel.comm import ParallelContext
    devices = jax.devices()
    if len(devices) < 2:
        raise RuntimeError(
            "data8 shape class needs the hermetic multi-device CPU backend "
            "(force_cpu_backend(device_count=8) before jax initializes)")
    pctx = ParallelContext("data", devices)
    D = pctx.num_devices
    F, B, N = 2 * D, 16, 32 * D
    spec = _wave_spec(num_features=F, num_leaves=7, hist_slots=3,
                      wave_size=3, chunk_rows=32)
    comm = pctx.make_comm(F)
    entry = get_entry("grower.wave_body")

    def grow_fn(X, g, h, inc, fok, iscat, nb, mc, db):
        return entry(X, g, h, inc, fok, iscat, nb, mc, db, spec, comm)

    sharded = pctx.shard_grow(grow_fn)
    return _wave_program(
        "data8", spec, N=N, grow=sharded, rows=N // D,
        comm_bytes=lambda: comm.collective_bytes(
            spec.hist_slots, B, use_categorical=False))


# ----------------------------------------------------- routing.bundle_space

def _routing_program(shape_class: str, efb_unpack: bool) -> TracedProgram:
    from lightgbm_tpu.grower import (BundleDecode, RouteTable,
                                     empty_route_table)
    route = get_entry("routing.bundle_space")
    N, G, F, B, Bb = 64, 3, 8, 8, 16
    spec = _wave_spec(num_leaves=7, num_features=F, num_bins_padded=B,
                      chunk_rows=32, hist_slots=3, wave_size=3, max_depth=-1,
                      min_data_in_leaf=1.0, min_sum_hessian_in_leaf=0.0,
                      efb_unpack=efb_unpack)
    bundle = BundleDecode(
        col=jnp.zeros(F, jnp.int32), lo=jnp.ones(F, jnp.int32),
        hi=jnp.full(F, 2, jnp.int32), off=jnp.zeros(F, jnp.int32),
        unpack_bin=jnp.zeros((F, B), jnp.int32),
        code_feat=jnp.zeros((G, Bb), jnp.int32))
    jx = jax.make_jaxpr(
        lambda X, lid, keys, rows, db: route(
            X, lid, RouteTable(keys, rows), None, spec, bundle, db))(
        jnp.zeros((N, G), jnp.uint8), jnp.zeros(N, jnp.int32),
        *empty_route_table(spec, bundle), jnp.zeros(F, jnp.int32))
    return TracedProgram("routing.bundle_space", shape_class, jx)


@program_builder("routing.bundle_space", "bundled")
def _routing_native():
    return _routing_program("bundled", efb_unpack=False)


@program_builder("routing.bundle_space", "bundled_unpack")
def _routing_unpack():
    # legacy decode arm: per-row take_along_axis through unpack_bin
    return _routing_program("bundled_unpack", efb_unpack=True)


# ----------------------------------------------------- grower.stream_legs

def _stream_grower():
    StreamedGrower = get_entry("grower.stream_legs")
    F, B, N = 6, 16, 128
    spec = _wave_spec(num_features=F, num_leaves=7, hist_slots=3,
                      wave_size=3, chunk_rows=32)
    sg = StreamedGrower(
        spec, None, None, n_rows_padded=N, local_shard_rows=32, n_shards=4,
        num_cols=F, code_mode="u8", num_bins=jnp.full(F, B, jnp.int32),
        missing_code=jnp.zeros(F, jnp.int32),
        default_bin=jnp.zeros(F, jnp.int32), is_cat=jnp.zeros(F, bool))
    return sg, F, N


def _stream_state():
    sg, F, N = _stream_grower()
    g = jnp.ones(N, jnp.float32)
    state, leaf_id, table0, map_mask0 = sg.init_fn(g, g, g)
    acc, comp = sg.zeros_fn()
    slot_of_leaf, leaf_of_slot = sg.slot_fn(state.needs_hist)
    return (sg, F, N, g, state, leaf_id, table0, map_mask0, acc, comp,
            slot_of_leaf, leaf_of_slot)


@program_builder("grower.stream_legs", "stream_shard")
def _stream_shard():
    (sg, F, N, g, _state, leaf_id, table0, map_mask0, acc, comp,
     slot_of_leaf, _los) = _stream_state()
    codes_sh = jnp.zeros((sg.local_shard_rows, F), jnp.uint8)
    jx = jax.make_jaxpr(sg.shard_fn)(
        acc, comp, codes_sh, leaf_id, g, g, g, slot_of_leaf, table0,
        map_mask0, np.int32(0))
    return TracedProgram("grower.stream_legs", "stream_shard", jx)


@program_builder("grower.stream_legs", "stream_wave")
def _stream_wave():
    (sg, F, _N, _g, state, _lid, _t0, _mm0, acc, _comp,
     _sol, leaf_of_slot) = _stream_state()
    jx = jax.make_jaxpr(sg.wave_fn)(state, acc, leaf_of_slot,
                                    jnp.ones(F, bool))
    return TracedProgram("grower.stream_legs", "stream_wave", jx)


# ------------------------------------------------------------- linear legs

@program_builder("linear.moments", "linear")
def _moments_program():
    acc = get_entry("linear.moments")
    N, F, L1, K = 128, 6, 8, 3
    jx = jax.make_jaxpr(
        lambda Xr, Xm, lid, lf, g, h, inc: acc(Xr, Xm, lid, lf, g, h, inc,
                                               64))(
        jnp.zeros((N, F), jnp.float32), jnp.zeros((N, F), bool),
        jnp.zeros(N, jnp.int32), jnp.zeros((L1, K), jnp.int32),
        jnp.zeros(N, jnp.float32), jnp.zeros(N, jnp.float32),
        jnp.ones(N, jnp.float32))
    return TracedProgram("linear.moments", "linear", jx)


@program_builder("linear.fit_leg", "linear")
def _fit_program():
    from lightgbm_tpu.grower import _empty_tree
    fit = get_entry("linear.fit_leg")
    L, B, N, F = 7, 8, 128, 6
    tree = _empty_tree(L, B)
    jx = jax.make_jaxpr(
        lambda t, Xr, Xm, lid, g, h, inc, iscat: fit(
            t, Xr, Xm, lid, g, h, inc, iscat, max_features=3,
            linear_lambda=0.01, chunk_rows=64, max_steps=4))(
        tree, jnp.zeros((N, F), jnp.float32), jnp.zeros((N, F), bool),
        jnp.zeros(N, jnp.int32), jnp.zeros(N, jnp.float32),
        jnp.zeros(N, jnp.float32), jnp.ones(N, jnp.float32),
        jnp.zeros(F, bool))
    return TracedProgram("linear.fit_leg", "linear", jx)


# ------------------------------------------------------ predict.forest_walk

@program_builder("predict.forest_walk", "serial")
def _predict_program():
    walk = get_entry("predict.forest_walk")
    T, N, F = 3, 32, 4
    M = 6
    i32 = jnp.int32
    jx = jax.make_jaxpr(walk)(
        jnp.zeros((T, M), i32), jnp.zeros((T, M), i32),
        jnp.zeros((T, M), i32), jnp.zeros((T, M), i32),
        jnp.zeros((T, M), i32), jnp.zeros(T, bool), jnp.zeros(F, i32),
        jnp.zeros((N, F), i32), jnp.zeros((N, F), bool),
        jnp.zeros((N, F), bool))
    return TracedProgram("predict.forest_walk", "serial", jx)


# ------------------------------------------------------- train_step.fused

def _booster(params=None, N: int = 256, F: int = 6):
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.rand(N, F).astype(np.float32)
    y = (X[:, 0] + 0.25 * rng.rand(N) > 0.6).astype(np.float32)
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1}
    p.update(params or {})
    return lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=1,
                     keep_training_booster=True)


@program_builder("train_step.fused", "serial")
def _train_step_program():
    get_entry("train_step.fused")      # assert the GBDT hook is registered
    bst = _booster()
    g = bst._gbdt
    # CPU gates donation off in the product path; the contract forces the
    # TPU-style donate set on so the HLO alias header is checkable here
    donate = (2, 3)
    step = g._make_step(donate_override=donate)
    consts, valid_Xb, valid_scores = g._dispatch_prep(
        float(g.config.learning_rate))
    args = (consts, valid_Xb, g.score, valid_scores, g.bag_mask,
            g._rng_key, g._iter_dev, g._shrink_cache[1])
    jx = jax.make_jaxpr(step)(*args)
    expected = len(jax.tree_util.tree_leaves((args[2], args[3])))
    return TracedProgram(
        "train_step.fused", "serial", jx,
        hlo=lambda: step.lower(*args).compile().as_text(),
        donate_argnums=donate, expected_aliases=expected)


# --------------------------------------------------------------- contracts

contract(
    "T001", "at most one row-sized sort a wave, in the compacted arm; no "
            "row-sized scatter or cumsum in the wave loop",
    "grower.wave_body",
    checks=[C.RowPassesInLoops(max_sorts=1)],
    targets=[Target("serial"), Target("u4_packed")],
    doc="A compacted pass builds its slot-grouped row index with one sort "
        "inside its arm of the cond; a streamed wave builds nothing. A "
        "permutation carried across waves and re-partitioned through a "
        "row-sized scatter hides a sort of its own on the TPU and cost half "
        "the tree (PERF.md, PR 28; deleted in PR 32). No shipped arm "
        "violates this any more: the planted cell TX94 of "
        "tests/fixtures/tpu_lint/trace_violations.py (a loop body with a "
        "row-sized scatter) keeps the check demonstrably sensitive.")

contract(
    "T002", "no gather in bundle-space routing", "routing.bundle_space",
    checks=[C.ForbidPrimitives({"gather"})],
    targets=[Target("bundled"), Target("bundled_unpack", "violates")],
    doc="Native EFB routes on the one-hot table; the legacy unpack arm "
        "keeps the per-row [F, B] decode gather as the sensitivity pin.")

contract(
    "T003", "data-parallel collectives match collective_bytes()",
    "grower.wave_body",
    checks=[C.RequiredCollectives()],
    targets=[Target("data8")],
    doc="Every collective the cost model charges must appear, and none it "
        "does not charge may appear.")

contract(
    "T004", "no silent f64 in the wave loop", "grower.wave_body",
    checks=[C.DtypeDiscipline()],
    targets=[Target("serial"), Target("u4_packed"), Target("data8")],
    doc="f64 belongs to hist_f64 Kahan sums and host accumulation only.")

contract(
    "T005", "train-step donation survives compilation", "train_step.fused",
    checks=[C.DonationEffective()],
    targets=[Target("serial")],
    doc="Donated score carries must alias outputs in the compiled "
        "executable's input_output_alias header.")

contract(
    "T006", "no host round-trips inside the fused step's loops",
    "train_step.fused",
    checks=[C.NoHostTransferInLoops(), C.DtypeDiscipline()],
    targets=[Target("serial")])

contract(
    "T007", "streamed legs stay sort-free and on-device",
    "grower.stream_legs",
    checks=[C.ForbidPrimitives({"sort"}), C.NoHostTransferInLoops(),
            C.DtypeDiscipline()],
    targets=[Target("stream_shard"), Target("stream_wave")])

contract(
    "T008", "linear-leaf moment accumulation is gather-free",
    "linear.moments",
    checks=[C.ForbidPrimitives({"gather"}), C.DtypeDiscipline()],
    targets=[Target("linear")],
    doc="Moments accumulate via the one-hot chunk contraction — a per-row "
        "feature gather here regresses the PR-14 design.")

contract(
    "T009", "one batched Cholesky per linear fit", "linear.fit_leg",
    checks=[C.CountPrimitive("cholesky", 1), C.DtypeDiscipline()],
    targets=[Target("linear")],
    doc="All leaves solve in ONE vmapped factorization; a second cholesky "
        "means the solve leg was duplicated instead of batched.")

contract(
    "T010", "forest walk is sort-free and loop-host-clean",
    "predict.forest_walk",
    checks=[C.ForbidPrimitives({"sort"}), C.NoHostTransferInLoops(),
            C.DtypeDiscipline()],
    targets=[Target("serial")])
