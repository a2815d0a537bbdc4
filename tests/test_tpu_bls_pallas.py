"""The running-sum add as one Pallas kernel (``tpu/bls.py``
``_running_add_pallas``) against its XLA formulation
(``_running_add_xla``): the same int32 limbs, bit for bit, along chains
of vote-shaped adds and on the complete formula's edge cases, in
interpret mode here; and the kernel compiled for a v5e, where an add is
one custom call and a few copies instead of ~200 operations."""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hotstuff_tpu.crypto.bls.curve import G1Point
from hotstuff_tpu.crypto.bls.fields import R
from hotstuff_tpu.tpu import bls as T

XLA = jax.jit(T._running_add_xla)
PALLAS = jax.jit(lambda *a: T._running_add_pallas(*a, interpret=True))


def identity():
    return (
        jnp.zeros((1, T.NLIMBS), jnp.int32),
        jnp.asarray(T.to_mont_limbs(1), jnp.int32).reshape(1, T.NLIMBS),
        jnp.zeros((1, T.NLIMBS), jnp.int32),
    )


def plain(pt: G1Point):
    """A vote's point as ``TpuG1RunningSum.add`` ships it."""
    z = np.zeros((1, T.NLIMBS), np.int32)
    z[0, 0] = 1
    return (
        jnp.asarray(T.ints_to_limbs_batch([pt.x])),
        jnp.asarray(T.ints_to_limbs_batch([pt.y])),
        jnp.asarray(z),
    )


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


def test_the_running_sum_takes_the_xla_program_off_the_chip():
    assert jax.default_backend() == "cpu"
    args = (*identity(), *plain(G1Point.generator()))
    assert same(T._running_add_kernel(*args), XLA(*args))


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
    # a program compiled for a described chip cannot be read back from
    # jax's cache without one: keep it out
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        row = jax.ShapeDtypeStruct((1, T.NLIMBS), jnp.int32, sharding=one_chip)
        compiled = jax.jit(T._running_add_pallas).lower(*[row] * 6).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
    text = compiled.as_text()
    entry = text[text.index("ENTRY") :]
    assert entry.count("custom-call(") == 1
    assert entry.count("fusion(") + entry.count("slice(") < 10
