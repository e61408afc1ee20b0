"""Planted trace-contract violations, exec'd via ``--trace --load``.

One deliberately-broken (entry, shape_class) cell per check kind —
forbidden-primitive, required-collective, dtype, donation, row-pass — proving
the trace tier FAILS when a contract is violated (the shipped tree passes
clean, so without these the tier's teeth would be untested). Contract ids
use the TX9x range so they can never collide with shipped T0xx ids.
"""
import jax
import jax.numpy as jnp

from lightgbm_tpu.analysis.contracts import (Target, TracedProgram,
                                             contract, program_builder)
from lightgbm_tpu.analysis.contracts import checks as C

ENTRY = "fixture.bad"


@program_builder(ENTRY, "sorty")
def _sorty():
    jx = jax.make_jaxpr(lambda x: jnp.sort(x))(jnp.zeros(8, jnp.float32))
    return TracedProgram(ENTRY, "sorty", jx)


contract("TX90", "planted forbidden-primitive violation", ENTRY,
         checks=[C.ForbidPrimitives({"sort"})], targets=[Target("sorty")])


@program_builder(ENTRY, "no_collective")
def _no_collective():
    # promises a psum in collective_bytes() but traces none
    jx = jax.make_jaxpr(lambda x: x + 1.0)(jnp.zeros(8, jnp.float32))
    return TracedProgram(ENTRY, "no_collective", jx,
                         comm={"psum_root_scalars": 4})


contract("TX91", "planted required-collective violation", ENTRY,
         checks=[C.RequiredCollectives()], targets=[Target("no_collective")])


@program_builder(ENTRY, "f64_leak")
def _f64_leak():
    with jax.enable_x64(True):
        jx = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2.0)(jnp.zeros(8, jnp.float32))
    return TracedProgram(ENTRY, "f64_leak", jx)


contract("TX92", "planted dtype violation", ENTRY,
         checks=[C.DtypeDiscipline()], targets=[Target("f64_leak")])


@program_builder(ENTRY, "dropped_donation")
def _dropped_donation():
    # donates a [16] input into a scalar output: no shape-compatible
    # output exists, so XLA records no alias — exactly the failure mode
    # the donation contract exists to catch
    f = jax.jit(lambda x: jnp.sum(x), donate_argnums=(0,))
    x = jnp.zeros(16, jnp.float32)
    return TracedProgram(
        ENTRY, "dropped_donation", jax.make_jaxpr(f)(x),
        hlo=lambda: f.lower(x).compile().as_text(),
        donate_argnums=(0,), expected_aliases=1)


contract("TX93", "planted donation violation", ENTRY,
         checks=[C.DonationEffective()], targets=[Target("dropped_donation")])


WAVE_ROWS = 64


@program_builder(ENTRY, "scattered_wave")
def _scattered_wave():
    # a wave loop that re-partitions a row permutation through a row-sized
    # scatter in EVERY iteration: what contract T001 (the same check, on
    # grower.wave_body) forbids, and what the carried leaf partition did
    # until PR 32 deleted it. On the TPU such a scatter hides a sort.
    def wave(carry):
        i, perm = carry
        dest = (perm + i) % WAVE_ROWS
        return i + 1, jnp.zeros_like(perm).at[dest].set(perm)

    jx = jax.make_jaxpr(lambda perm: jax.lax.while_loop(
        lambda c: c[0] < 3, wave, (jnp.asarray(0, jnp.int32), perm)))(
        jnp.arange(WAVE_ROWS, dtype=jnp.int32))
    return TracedProgram(ENTRY, "scattered_wave", jx, rows=WAVE_ROWS)


contract("TX94", "planted row-pass violation (T001's check)", ENTRY,
         checks=[C.RowPassesInLoops(max_sorts=1)],
         targets=[Target("scattered_wave")])
