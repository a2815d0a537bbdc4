"""Pallas TPU kernel for the fused double-scalar multiplication.

This is the VMEM-resident rewrite of ``curve.dual_scalar_mult`` — the
hot loop of batched Ed25519 verification (reference hot spot:
``Signature::verify_batch``, crypto/src/lib.rs:213-226).  The XLA
version is HBM-bound: every field op round-trips intermediates through
HBM, and slope-timing on hardware shows elementwise throughput pinned
at memory bandwidth.  Here the whole 32-macro-step Straus scan runs
inside ONE kernel with every intermediate in VMEM.

Layout: limb-major ``[NLIMBS, Bt]`` — the batch tile rides the 128-wide
lane dimension (full VPU utilization), limbs ride sublanes.  The
schoolbook-product collapse is an int32 diagonal sum on the VPU (see
_mul_t — it replaced the round-2 one-hot MXU matmul, whose ~2.5%-dense
weight matrix burned ~40x the useful MACs and dominated the kernel).
Per-batch table selects use a 4-level tournament of ``jnp.where``
(15 selects of a [4, 20, Bt] entry vs 16 one-hot multiply-adds).
Constant inputs (base-point table, curve constant, subtraction pad) are
kernel INPUTS — Pallas kernels cannot capture traced constants — mapped
to block (0, 0) so every grid tile reads the same copy.

The production kernel is FULLY fused (round 3): the Straus scan AND the
compressed-encoding comparison (Fermat inversion, canonicalization,
y/sign compare) run in one Pallas dispatch — the former XLA epilogue
was ~265 sequential HBM round-trips, ~2 ms of the 256-vote QC's device
time.  Correctness oracle: ``curve.dual_scalar_mult`` + 
``curve.compressed_equals`` (RFC-8032-vector-tested); parity is tested
in interpret mode on CPU and on device in tests/test_tpu_ed25519.py.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..crypto import ed25519_ref as ref
from . import curve, field as F

NL = F.NLIMBS  # 20
NCOLS = 2 * NL - 1  # 39
LANE_TILE = 128  # minimum batch tile (lane width)
# Batch tile.  128 (one lane width) since round 3: the kernel is
# VPU-THROUGHPUT-bound — slope-timing at 128/256/512 lanes measured
# 1.83/3.28/6.47 ms, ~linear in lanes (pre-chip rig) —
# so narrower tiles cost nothing, and the round-3 wave batching (which
# roughly triples per-tile transients: the mul waves materialize
# [NL, NL, 4*Bt] outer products) blows the 16M scoped-VMEM cap at 256
# lanes (21.7M, pre-chip rig).
BT = 128

# A 512-lane "wide tile" for the split kernel (one 16-step scan for a
# 256-signature QC) existed through round 2 and was DELETED in round 3:
# the same linear-in-lanes measurement shows a 512-lane 16-step scan
# can never beat two 256-lane tiles, and its Mosaic compile never
# finished (~58 min, aborted) anyway.


_HIGH = jax.lax.Precision.HIGHEST

# Host-side constants (numpy; shipped to the kernel as inputs).


def _bake_t2d(table: np.ndarray) -> np.ndarray:
    """Copy of a [n, 4, NL] base table with the T column premultiplied
    by the curve constant 2d.  Table points are only ever the ``q``
    operand of ``_point_add_t``, whose c-term is 2d*T1*T2 — baking 2d
    into T2 turns that into the single mul T1*T2d and removes one field
    mul from EVERY table addition in the scan."""
    out = table.copy()
    d2_int = 2 * ref.D % ref.P
    for m in range(out.shape[0]):
        x = F.int_from_limbs(out[m, 0])
        y = F.int_from_limbs(out[m, 1])
        out[m, 3] = F.limbs_from_int(x * y % ref.P * d2_int % ref.P)
    return out


_BTAB_T = (
    _bake_t2d(np.asarray(curve.B_TABLE8))  # [256, 4, 20], T -> T*2d
    .astype(np.float32)
    .reshape(1 << curve.B_WINDOW, 4 * NL)
    .T.copy()
)  # [80, 256]; limb values < 2^13+608 are f32-exact
_D2_COL = curve.D2_LIMBS.reshape(NL, 1)  # curve constant 2d, limb-major
_SUBPAD_COL = F.SUB_PAD.reshape(NL, 1)


class _Env:
    """Kernel-side handles to the constant inputs."""

    def __init__(self, btab, d2, subpad):
        self.btab = btab  # [80, 256] f32
        self.d2 = d2  # [NL, 1] int32
        self.subpad = subpad  # [NL, 1] int32


# ---- limb-major field ops (values, not refs; all [NL, Bt]) -----------------


def _carry_t(z, passes: int):
    """Parallel carry passes along axis -2 (the limb axis)."""
    if z.shape[-2] > NL:
        lo = z[..., :NL, :]
        hi = z[..., NL:, :]
        hi_lo = (hi & F.MASK) * F.FOLD
        hi_hi = (hi >> F.LIMB_BITS) * F.FOLD
        nhi = z.shape[-2] - NL
        pad = [(0, 0)] * (z.ndim - 2)
        add0 = jnp.pad(hi_lo, pad + [(0, NL - nhi), (0, 0)])
        add1 = jnp.pad(hi_hi, pad + [(1, NL - nhi - 1), (0, 0)])
        z = lo + add0 + add1
    for _ in range(passes):
        r = jnp.concatenate(
            [z[..., : NL - 1, :] & F.MASK, z[..., NL - 1 :, :] & F.TOP_MASK],
            axis=-2,
        )
        c = z[..., : NL - 1, :] >> F.LIMB_BITS
        c_top = (z[..., NL - 1 :, :] >> F.TOP_SHIFT) * F.TOP_FOLD
        z = jnp.concatenate([r[..., :1, :] + c_top, r[..., 1:, :] + c], axis=-2)
    return z


def _mul_t(env, a, b):
    """[NL, Bt] x [NL, Bt] -> [NL, Bt]; int32 diagonal collapse.

    The schoolbook product sum out[c] = sum_{i+j=c} a_i*b_j used to ride
    the MXU as a one-hot f32 matmul ([39,400]@[400,Bt], with the lo/hi
    13-bit split for f32 exactness).  That matrix is ~2.5% dense — each
    of the 400 products feeds exactly ONE output column — so the MXU
    burns ~40x the useful MACs, and at QC tile widths the two dots
    dominated the whole kernel.  The diagonal sum is 20 shifted int32
    adds on the VPU instead, with NO lo/hi split or f32 conversions:
    products are exact in int32 (limbs < 2^13+608 -> products < 2^26.3,
    20-term column sums < 2^30.6 < 2^31), and the value handed to
    _carry_t is bit-identical to what the matmul produced, so the carry
    bound analysis is unchanged."""
    outer = a[:, None, :] * b[None, :, :]  # [NL, NL, Bt]
    total = None
    for i in range(NL):
        shifted = jnp.pad(outer[i], [(i, NL - 1 - i), (0, 0)])  # [39, Bt]
        total = shifted if total is None else total + shifted
    return _carry_t(total, passes=4)


def _add_t(a, b):
    return _carry_t(a + b, passes=2)


def _sub_t(env, a, b):
    return _carry_t(a + (env.subpad - b), passes=2)


def _dbl_small_t(a):
    return _carry_t(a * jnp.int32(2), passes=2)


# ---- wave batching ----------------------------------------------------------
#
# At QC-shaped tiles ([NL, 128..512]) every field op is a handful of
# vregs, so the kernel is dominated by per-op issue overhead, not
# arithmetic.  The point formulas have natural 3-4-wide independent
# "waves" of muls (e.g. add-2008-hwcd-3's a, b, t1*t2, z1*z2); lane-
# concatenating a wave runs ONE outer product + ONE [39,400]@[400,n*Bt]
# MXU collapse + ONE carry chain over all of them, quadrupling the work
# per vector instruction at identical per-column math (the carry bound
# analysis is unchanged — columns never interact).


def _mul_wave_t(env, pairs):
    """len(pairs) independent [NL, Bt] products as one batched _mul_t."""
    if len(pairs) == 1:
        return [_mul_t(env, *pairs[0])]
    bt = pairs[0][0].shape[-1]
    a = jnp.concatenate([p[0] for p in pairs], axis=-1)
    b = jnp.concatenate([p[1] for p in pairs], axis=-1)
    prod = _mul_t(env, a, b)
    return [prod[..., i * bt : (i + 1) * bt] for i in range(len(pairs))]


def _lin_wave_t(terms, bt):
    """Batched 2-pass carry over pre-formed linear combinations.  Each
    term must be exactly one of the forms _add_t/_sub_t/_dbl_small_t
    carry today (x + y, x + (subpad - y), 2*x of carried values) so the
    2-pass bound argument applies column-by-column unchanged."""
    z = jnp.concatenate(terms, axis=-1)
    z = _carry_t(z, passes=2)
    return [z[..., i * bt : (i + 1) * bt] for i in range(len(terms))]


# ---- limb-major point ops: points are [4, NL, Bt] stacks (X, Y, Z, T) ------


def _point_add_t(env, p, q, need_t: bool = True):
    """Unified extended-coordinate addition (add-2008-hwcd-3), waved.

    ``p`` is an accumulator with a PLAIN T coordinate; ``q`` is a table
    point whose T is premultiplied by 2d (_bake_t2d / the in-kernel
    entry conversion), so the c-term is the single mul t1*t2d inside
    wave 1.

    ``need_t=False`` skips producing the T coordinate (one mul slot in
    wave 2): doublings ignore their input's T, so an addition feeding a
    doubling run — or the final scan output, which only X/Y/Z reach —
    never needs it.  The slot is zero-filled to keep the stack shape."""
    x1, y1, z1, t1 = p[0], p[1], p[2], p[3]
    x2, y2, z2, t2 = q[0], q[1], q[2], q[3]
    bt = x1.shape[-1]
    dm1, sm1, dm2, sm2 = _lin_wave_t(
        [
            y1 + (env.subpad - x1),
            y1 + x1,
            y2 + (env.subpad - x2),
            y2 + x2,
        ],
        bt,
    )
    a, b, c, zz = _mul_wave_t(
        env, [(dm1, dm2), (sm1, sm2), (t1, t2), (z1, z2)]
    )
    d = _dbl_small_t(zz)
    e, f, g, h = _lin_wave_t(
        [
            b + (env.subpad - a),
            d + (env.subpad - c),
            d + c,
            b + a,
        ],
        bt,
    )
    prods = _mul_wave_t(
        env, [(e, f), (g, h), (f, g)] + ([(e, h)] if need_t else [])
    )
    t_out = prods[3] if need_t else jnp.zeros_like(prods[0])
    return jnp.stack([prods[0], prods[1], prods[2], t_out])


def _point_double_t(env, p, need_t: bool = True):
    """dbl-2008-hwcd, waved (all four wave-1 operands are squares).
    ``need_t=False`` as in _point_add_t: only the LAST doubling of a run
    (whose output feeds an addition) must produce T."""
    x1, y1, z1 = p[0], p[1], p[2]
    bt = x1.shape[-1]
    xy = _add_t(x1, y1)
    a, b, zz, xy2 = _mul_wave_t(
        env, [(x1, x1), (y1, y1), (z1, z1), (xy, xy)]
    )
    c = _dbl_small_t(zz)
    h, g = _lin_wave_t([a + b, a + (env.subpad - b)], bt)
    e, f = _lin_wave_t([h + (env.subpad - xy2), c + g], bt)
    prods = _mul_wave_t(
        env, [(e, f), (g, h), (f, g)] + ([(e, h)] if need_t else [])
    )
    t_out = prods[3] if need_t else jnp.zeros_like(prods[0])
    return jnp.stack([prods[0], prods[1], prods[2], t_out])


def _identity_t(bt):
    zeros = jnp.zeros((NL, bt), jnp.int32)
    # iota mask instead of .at[].set — scatter has no Mosaic lowering
    limb0 = jax.lax.broadcasted_iota(jnp.int32, (NL, bt), 0) == 0
    one = jnp.where(limb0, 1, 0)
    return jnp.stack([zeros, one, one, zeros])


def _build_entries_t(env, a_point, bt):
    """A-multiples table [0]A..[15]A for the tournament select.

    The chain is built with PLAIN-T points (each add's p operand), with
    q = A carrying T*2d; at the end every entry's T is converted to T*2d
    in ONE wide mul against the broadcast d2 column, because entries are
    only ever consumed as the q operand of _point_add_t (identity's T2d
    is 0, so it needs no conversion)."""
    a2d = jnp.stack(
        [
            a_point[0],
            a_point[1],
            a_point[2],
            _mul_t(env, a_point[3], env.d2),
        ]
    )
    chain = [a_point]
    for _ in range(2, 1 << curve.WINDOW):
        chain.append(_point_add_t(env, chain[-1], a2d))
    ts2d = _mul_t(env, jnp.concatenate([c[3] for c in chain], axis=-1), env.d2)
    return [_identity_t(bt)] + [
        jnp.stack([c[0], c[1], c[2], ts2d[..., i * bt : (i + 1) * bt]])
        for i, c in enumerate(chain)
    ]


def _tournament_select(entries, nibble):
    """entries: list of 16 [4, NL, Bt] points; nibble: [1, Bt] int32.
    4-level tournament of jnp.where — 15 selects instead of 16
    one-hot multiply-accumulates."""
    level = entries
    for bit in range(curve.WINDOW):
        mask = ((nibble >> bit) & 1)[None, :, :] != 0  # [1, 1, Bt]
        level = [
            jnp.where(mask, hi, lo)
            for lo, hi in zip(level[0::2], level[1::2])
        ]
    return level[0]


def _select_base_t(env, byte, bt):
    """Constant-table select via one-hot MXU matmul: [80, nent] @
    [nent, Bt] -> [4, NL, Bt] (nent = 256)."""
    nent = env.btab.shape[1]
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (nent, bt), 0) == byte
    ).astype(jnp.float32)
    sel = jax.lax.dot(
        env.btab, onehot, precision=_HIGH, preferred_element_type=jnp.float32
    )
    return sel.astype(jnp.int32).reshape(4, NL, bt)


# ---- in-kernel compressed-equality epilogue --------------------------------
#
# The XLA epilogue (curve.compressed_equals: Fermat inversion + canonical
# + compare) is ~265 SEQUENTIAL tiny ops on [batch, 20] arrays — each one
# an HBM round-trip, measured ~2 ms of the 256-vote QC's 5.2 ms device
# time (the Pallas scan itself is 3.3 ms).  Running the same chain inside
# the kernel keeps every intermediate in VMEM (~0.3 ms).  Limb-major
# ports of field.py's _chain/_strict/canonical/pow_inv (field.py:238-308);
# limbs ride axis -2 with static indices, so no gathers are needed.


def _chain_seq_t(z):
    """One sequential carry pass along the limb axis (field.py _chain)."""
    c = jnp.zeros_like(z[..., :1, :])
    outs = []
    for i in range(NL):
        x = z[..., i : i + 1, :] + c
        c = x >> F.LIMB_BITS  # arithmetic shift: floor for negatives
        outs.append(x & F.MASK)
    return outs, c


def _strict_t(z):
    """Loose-normalized -> strictly normalized (field.py _strict)."""
    outs, _ = _chain_seq_t(z)
    z = jnp.concatenate(outs, axis=-2)
    for _ in range(2):  # peel bit 255 (at most twice)
        top = z[..., NL - 1 :, :] >> F.TOP_SHIFT
        z = jnp.concatenate(
            [
                z[..., :1, :] + top * F.TOP_FOLD,
                z[..., 1 : NL - 1, :],
                z[..., NL - 1 :, :] - (top << F.TOP_SHIFT),
            ],
            axis=-2,
        )
        outs, _ = _chain_seq_t(z)
        z = jnp.concatenate(outs, axis=-2)
    return z


def _canonical_t(a):
    """Unique value in [0, p) (field.py canonical), limb-major."""
    a = _strict_t(a)
    for _ in range(2):
        borrow = jnp.zeros_like(a[..., :1, :])
        outs = []
        for i in range(NL):
            x = a[..., i : i + 1, :] - int(F.P_LIMBS[i]) + borrow
            borrow = x >> F.LIMB_BITS
            outs.append(x & F.MASK)
        diff = jnp.concatenate(outs, axis=-2)
        a = jnp.where(borrow >= 0, diff, a)  # no final borrow -> a >= p
    return a


def _pow_inv_t(env, a):
    """a^(p-2) = a^-1, the standard curve25519 chain (field.py pow_inv).

    The long squaring runs are ``fori_loop``s, NOT unrolled: unrolling
    puts ~254 full multiplier bodies into one Mosaic kernel, which blew
    both the compile time (>35 min, aborted) and the scoped-VMEM stack
    (21.7M > 16M cap) when this epilogue was first fused in."""

    def sqr_n(x, n):
        if n < 4:
            for _ in range(n):
                x = _mul_t(env, x, x)
            return x
        return jax.lax.fori_loop(0, n, lambda i, v: _mul_t(env, v, v), x)

    z2 = _mul_t(env, a, a)
    z9 = _mul_t(env, sqr_n(z2, 2), a)
    z11 = _mul_t(env, z9, z2)
    z2_5_0 = _mul_t(env, _mul_t(env, z11, z11), z9)
    z2_10_0 = _mul_t(env, sqr_n(z2_5_0, 5), z2_5_0)
    z2_20_0 = _mul_t(env, sqr_n(z2_10_0, 10), z2_10_0)
    z2_40_0 = _mul_t(env, sqr_n(z2_20_0, 20), z2_20_0)
    z2_50_0 = _mul_t(env, sqr_n(z2_40_0, 10), z2_10_0)
    z2_100_0 = _mul_t(env, sqr_n(z2_50_0, 50), z2_50_0)
    z2_200_0 = _mul_t(env, sqr_n(z2_100_0, 100), z2_100_0)
    z2_250_0 = _mul_t(env, sqr_n(z2_200_0, 50), z2_50_0)
    return _mul_t(env, sqr_n(z2_250_0, 5), z11)


def _compressed_equals_t(env, p, r_y, r_sign):
    """Does each lane of ``p`` (X, Y, Z rows of a [4, NL, Bt] stack)
    compress to (r_y, r_sign)?  Returns int32 [1, Bt] 0/1.  Same
    semantics as curve.compressed_equals — r_y is the RAW 13-bit split
    of the encoding's low 255 bits (never reduced), so non-canonical
    encodings can never match."""
    zinv = _pow_inv_t(env, p[2])
    x, y = _mul_wave_t(env, [(p[0], zinv), (p[1], zinv)])
    y_ok = jnp.all(_canonical_t(y) == r_y, axis=-2, keepdims=True)
    sign_ok = (_canonical_t(x)[..., :1, :] & 1) == r_sign
    return (y_ok & sign_ok).astype(jnp.int32)


# ---- the kernel ------------------------------------------------------------


def _dsm_scan(env, ax, ay, az, at, s_bytes, k_hi, k_lo):
    """The 32-macro-step Straus scan: P = [s]B + [k]A for one tile.
    Returns the accumulator stack [4, NL, Bt] (T not computed).

    ax..at: [NL, Bt] limbs of A (the negated public keys).
    s_bytes: [NWIN/2, Bt] MSB-first 8-bit windows of s.
    k_hi, k_lo: [NWIN/2, Bt] MSB-first 4-bit window pairs of k.
    """
    bt = ax.shape[-1]
    a_point = jnp.stack([ax[:], ay[:], az[:], at[:]])

    entries = _build_entries_t(env, a_point, bt)

    nsteps = curve.NWIN // 2

    def step(i, acc):
        # dynamic row reads from the refs (dynamic_slice on values has
        # no Mosaic lowering; ref indexing with pl.ds does)
        sb = s_bytes[pl.ds(i, 1), :]  # [1, Bt]
        wh = k_hi[pl.ds(i, 1), :]
        wl = k_lo[pl.ds(i, 1), :]
        # need_t schedule: doublings ignore input T, additions consume
        # it — so only the last doubling of each run and the addition
        # feeding another addition produce T (8 muls saved per step)
        for j in range(curve.WINDOW):
            acc = _point_double_t(env, acc, need_t=j == curve.WINDOW - 1)
        acc = _point_add_t(
            env, acc, _tournament_select(entries, wh), need_t=False
        )
        for j in range(curve.WINDOW):
            acc = _point_double_t(env, acc, need_t=j == curve.WINDOW - 1)
        acc = _point_add_t(env, acc, _tournament_select(entries, wl))
        acc = _point_add_t(env, acc, _select_base_t(env, sb, bt), need_t=False)
        return acc

    return jax.lax.fori_loop(0, nsteps, step, _identity_t(bt))


def _dsm_kernel(
    btab, d2, subpad, ax, ay, az, at, s_bytes, k_hi, k_lo, ox, oy, oz, ot
):
    """Coordinate-output tile kernel (parity tests; the production
    verify path uses _dsm_verify_kernel, which fuses the epilogue)."""
    env = _Env(btab[:], d2[:], subpad[:])
    out = _dsm_scan(env, ax, ay, az, at, s_bytes, k_hi, k_lo)
    ox[:] = out[0]
    oy[:] = out[1]
    oz[:] = out[2]
    ot[:] = out[3]


def _dsm_verify_kernel(
    btab, d2, subpad, ax, ay, az, at, s_bytes, k_hi, k_lo, r_y, r_sign, ok
):
    """Fused tile kernel: Straus scan + in-VMEM compressed-equality.
    r_y: [NL, Bt] raw limb split of each R encoding; r_sign: [1, Bt];
    ok: [1, Bt] int32 0/1 output."""
    env = _Env(btab[:], d2[:], subpad[:])
    out = _dsm_scan(env, ax, ay, az, at, s_bytes, k_hi, k_lo)
    ok[:] = _compressed_equals_t(env, out, r_y[:], r_sign[:])


@partial(jax.jit, static_argnames=("interpret",))
def dual_scalar_mult(s_win, k_win, a_point, *, interpret: bool = False):
    """Drop-in for curve.dual_scalar_mult, Pallas-accelerated.

    s_win, k_win: int32 [NWIN, batch] MSB-first 4-bit windows.
    a_point: (X, Y, Z, T) with coords [batch, NL].
    Returns (X, Y, Z, T) with coords [batch, NL] — T is NOT computed
    (zeros): the only consumer, compressed_equals, reads X/Y/Z, and the
    scan's need_t schedule skips the final extended coordinate (one mul
    per point op saved).
    batch must be a multiple of LANE_TILE (the BatchVerifier pads).
    """
    batch = s_win.shape[1]
    bt = BT if batch % BT == 0 else LANE_TILE
    if batch % bt:
        raise ValueError(f"batch {batch} not a multiple of {bt}")

    # pair 4-bit windows into the kernel's layout
    s_pairs = s_win.reshape(curve.NWIN // 2, 2, batch)
    s_bytes = s_pairs[:, 0] * (1 << curve.WINDOW) + s_pairs[:, 1]
    k_pairs = k_win.reshape(curve.NWIN // 2, 2, batch)

    coords_t = [jnp.transpose(c) for c in a_point]  # [NL, batch]

    grid = (batch // bt,)

    def const_spec(shape):
        return pl.BlockSpec(
            shape, lambda i: (0, 0), memory_space=pltpu.VMEM
        )

    limb_spec = pl.BlockSpec(
        (NL, bt), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    win_spec = pl.BlockSpec(
        (curve.NWIN // 2, bt), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    out_shape = jax.ShapeDtypeStruct((NL, batch), jnp.int32)

    ox, oy, oz, ot = pl.pallas_call(
        _dsm_kernel,
        grid=grid,
        in_specs=[
            const_spec(_BTAB_T.shape),
            const_spec(_D2_COL.shape),
            const_spec(_SUBPAD_COL.shape),
        ]
        + [limb_spec] * 4
        + [win_spec] * 3,
        out_specs=[limb_spec] * 4,
        out_shape=[out_shape] * 4,
        interpret=interpret,
    )(
        jnp.asarray(_BTAB_T),
        jnp.asarray(_D2_COL),
        jnp.asarray(_SUBPAD_COL),
        *coords_t,
        s_bytes,
        k_pairs[:, 0],
        k_pairs[:, 1],
    )

    return tuple(jnp.transpose(c) for c in (ox, oy, oz, ot))


@partial(jax.jit, static_argnames=("interpret",))
def verify_compressed(
    s_win, k_win, a_point, r_y, r_sign, *, interpret: bool = False
):
    """Fused production path: dual_scalar_mult + compressed_equals in ONE
    Pallas dispatch.  Same operand contract as dual_scalar_mult, plus
    r_y [batch, NL] (raw limb split of each R encoding's low 255 bits)
    and r_sign [batch] (bit 255).  Returns bool [batch].

    Why fused: the XLA epilogue is ~265 SEQUENTIAL tiny field ops
    (Fermat inversion + canonical), each an HBM round-trip — measured
    ~2 ms of the 256-vote QC's device time; in-VMEM it is ~0.3 ms."""
    batch = s_win.shape[1]
    bt = BT if batch % BT == 0 else LANE_TILE
    if batch % bt:
        raise ValueError(f"batch {batch} not a multiple of {bt}")

    s_pairs = s_win.reshape(curve.NWIN // 2, 2, batch)
    s_bytes = s_pairs[:, 0] * (1 << curve.WINDOW) + s_pairs[:, 1]
    k_pairs = k_win.reshape(curve.NWIN // 2, 2, batch)
    coords_t = [jnp.transpose(c) for c in a_point]  # [NL, batch]

    grid = (batch // bt,)

    def const_spec(shape):
        return pl.BlockSpec(shape, lambda i: (0, 0), memory_space=pltpu.VMEM)

    limb_spec = pl.BlockSpec(
        (NL, bt), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    win_spec = pl.BlockSpec(
        (curve.NWIN // 2, bt), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    row_spec = pl.BlockSpec((1, bt), lambda i: (0, i), memory_space=pltpu.VMEM)

    (ok,) = pl.pallas_call(
        _dsm_verify_kernel,
        grid=grid,
        in_specs=[
            const_spec(_BTAB_T.shape),
            const_spec(_D2_COL.shape),
            const_spec(_SUBPAD_COL.shape),
        ]
        + [limb_spec] * 4
        + [win_spec] * 3
        + [limb_spec, row_spec],
        out_specs=[row_spec],
        out_shape=[jax.ShapeDtypeStruct((1, batch), jnp.int32)],
        interpret=interpret,
    )(
        jnp.asarray(_BTAB_T),
        jnp.asarray(_D2_COL),
        jnp.asarray(_SUBPAD_COL),
        *coords_t,
        s_bytes,
        k_pairs[:, 0],
        k_pairs[:, 1],
        jnp.transpose(r_y),
        r_sign.reshape(1, batch),
    )
    return ok[0] != 0
