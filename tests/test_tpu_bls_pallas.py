"""The running-sum add as one Pallas kernel (``tpu/bls.py``
``_running_add_pallas``) against its XLA formulation
(``_running_add_xla``): the same int32 limbs, bit for bit, along chains
of vote-shaped adds and on the complete formula's edge cases, in
interpret mode here; the fold of staged votes as one Pallas kernel
(``_running_fold_pallas``) against those single adds; and both kernels
compiled for a v5e, where a call is one custom call and a few copies
instead of ~200 operations an add."""

import random
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hotstuff_tpu.crypto.bls.curve import G1Point
from hotstuff_tpu.crypto.bls.fields import R
from hotstuff_tpu.tpu import bls as T

from .test_tpu_bls import FOLD_SIZES, check_fold_is_single_adds
from .test_tpu_bls import identity_rows as identity
from .test_tpu_bls import plain_rows as plain

XLA = jax.jit(T._running_add_xla)
PALLAS = jax.jit(lambda *a: T._running_add_pallas(*a, interpret=True))


def same(a, b) -> bool:
    return all(np.array_equal(np.asarray(u), np.asarray(v)) for u, v in zip(a, b))


def affine(acc) -> G1Point:
    return T.projective_to_affine(
        *(np.asarray(c).reshape(T.NLIMBS) for c in acc)
    )


@pytest.mark.parametrize("seed", range(3))
def test_a_chain_of_adds_is_the_xla_programs_bit_for_bit(seed):
    """43 adds, a QC's worth, of random multiples of G: every
    intermediate accumulator equal limb for limb, and the sum the
    host's."""
    rng = random.Random(0x61A55 + seed)
    g = G1Point.generator()
    acc_xla = acc_pallas = identity()
    total = G1Point.identity()
    for _ in range(43):
        pt = g.mul(rng.randrange(1, R))
        total = total + pt
        acc_xla = XLA(*acc_xla, *plain(pt))
        acc_pallas = PALLAS(*acc_pallas, *plain(pt))
        assert same(acc_xla, acc_pallas)
    assert affine(acc_pallas) == total


def test_the_complete_formulas_edge_cases():
    """Identity plus a point, a point plus itself, a point plus its
    negation: the same limbs as the XLA program, and the right sums."""
    p = G1Point.generator().mul(0xB15)
    once = PALLAS(*identity(), *plain(p))
    assert same(once, XLA(*identity(), *plain(p))) and affine(once) == p
    twice = PALLAS(*once, *plain(p))
    assert same(twice, XLA(*once, *plain(p))) and affine(twice) == p + p
    gone = PALLAS(*once, *plain(-p))
    assert same(gone, XLA(*once, *plain(-p))) and affine(gone).inf


def test_loose_limbs_as_the_xla_program():
    """Accumulator limbs anywhere in the signed-loose range a stored sum
    can hold: the kernel's int32 arithmetic is the XLA program's."""
    rs = np.random.default_rng(0x61)
    for _ in range(8):
        args = [
            jnp.asarray(rs.integers(-8192, 8224, (1, T.NLIMBS)), jnp.int32)
            for _ in range(6)
        ]
        assert same(PALLAS(*args), XLA(*args))


def staged(points):
    """The fold's buffer with ``points`` in its first rows."""
    rows = np.zeros((T.FOLD_ROWS, 2, T.NLIMBS), np.int32)
    for i, pt in enumerate(points):
        rows[i] = T.ints_to_limbs_batch([pt.x, pt.y])
    return rows


def test_the_running_sum_takes_the_xla_program_off_the_chip():
    """Off a TPU the running sum's program is the XLA fold, no Pallas
    call in it: one staged row gives the single XLA add's limbs."""
    assert jax.default_backend() == "cpu"
    pt = G1Point.generator()
    args = (*identity(), staged([pt]), np.int32(1))
    assert "pallas_call" not in str(jax.make_jaxpr(T._running_add_impl)(*args))
    assert same(T._running_add_kernel(*args), XLA(*identity(), *plain(pt)))


#: the interpreted Pallas fold, as ``TpuG1RunningSum`` jits it, kept and
#: donated (compiled once a process)
PALLAS_FOLD = {
    donate: jax.jit(
        partial(T._running_fold_pallas, interpret=True),
        donate_argnums=(0, 1, 2) if donate else (),
    )
    for donate in (False, True)
}


@pytest.mark.parametrize("donate", [False, True], ids=["kept", "donated"])
@pytest.mark.parametrize("n", FOLD_SIZES)
def test_the_pallas_fold_is_successive_single_adds(n, donate, monkeypatch):
    """The running sum with the Pallas fold in its place (interpreted):
    the accumulator's limbs are those of ``n`` calls of the single-add
    kernel, an identity skipped among the points, stale rows of an
    earlier fold behind the count."""
    check_fold_is_single_adds(
        n, donate, monkeypatch, fold=PALLAS_FOLD[donate], single=PALLAS
    )


@pytest.mark.parametrize("kind", ["xla", "pallas"])
def test_rows_past_the_count_are_not_read(kind):
    """Garbage in every row from the count on changes nothing: a fold of
    five rows over a buffer of random limbs past them is the fold of the
    five over zeros."""
    fold = {"xla": jax.jit(T._running_fold_xla), "pallas": PALLAS_FOLD[False]}[kind]
    rs = np.random.default_rng(0x5EED)
    points = [G1Point.generator().mul(k) for k in (3, 5, 7, 11, 13)]
    clean = staged(points)
    dirty = clean.copy()
    dirty[5:] = rs.integers(-(2**31), 2**31 - 1, dirty[5:].shape)
    want = fold(*identity(), clean, np.int32(5))
    assert same(fold(*identity(), dirty, np.int32(5)), want)
    assert affine(want) == G1Point.generator().mul(3 + 5 + 7 + 11 + 13)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernel_compiles_for_a_v5e_as_one_custom_call(one_chip):
    """The single add and the fold the running sum dispatches (its
    accumulator donated, as on a TPU): each one custom call and a few
    copies."""
    # a program compiled for a described chip cannot be read back from
    # jax's cache without one: keep it out
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        row = jax.ShapeDtypeStruct((1, T.NLIMBS), jnp.int32, sharding=one_chip)
        rows = jax.ShapeDtypeStruct(
            (T.FOLD_ROWS, 2, T.NLIMBS), jnp.int32, sharding=one_chip
        )
        count = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        compiled = [
            jax.jit(T._running_add_pallas).lower(*[row] * 6).compile(),
            jax.jit(T._running_fold_pallas, donate_argnums=(0, 1, 2))
            .lower(row, row, row, rows, count)
            .compile(),
        ]
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
    for program in compiled:
        text = program.as_text()
        entry = text[text.index("ENTRY") :]
        assert entry.count("custom-call(") == 1
        assert entry.count("fusion(") + entry.count("slice(") < 10
