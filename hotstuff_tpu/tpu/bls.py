"""BLS12-381 G1 aggregation on TPU — the threshold-variant device path.

Implements the device side of docs/BLS_TPU_DESIGN.md: the G1 sum of a
QC's vote signatures, kept on the device as a running sum that folds
in its staged votes 64 at a time (``TpuG1RunningSum``), and the
batched scalar ladders of the opt-in timeout-storm offload
(``TpuStormOffload``).  The per-QC pairing
equality stays on the host (crypto/bls/pairing.py), where it is one
constant-cost call.

Two design changes vs the original design note, found during
implementation:

1. **Field reduction.**  The note proposed reusing the Ed25519
   fold-constant reduction with a fold *vector* for q.  That does not
   converge: q is not pseudo-Mersenne, so 2^390 mod q is itself 381 bits
   and each fold pass removes only ~9 bits.  Fq instead uses
   **Montgomery arithmetic in CIOS form, vectorized over the batch**:
   30 limbs of 13 bits (30x13 = 390 >= 381) in int32.  The limb
   recurrence is sequential (30 steps, each a full-width batched
   multiply-accumulate) with lazy column accumulators; only the limb-0
   carry is propagated exactly per step (the quotient digit m needs just
   the exact low 13 bits: m = ((t0 & MASK) * mu) & MASK), and a parallel
   carry pass every 8 steps keeps every column inside int32.

2. **Point formulas.**  Jacobian addition needs P==Q / P==-Q / identity
   case analysis, and deciding "h == 0 (mod q)" on device costs a full
   canonicalization per addition.  Instead points are homogeneous
   projective (X : Y : Z) with the **complete addition formulas of
   Renes-Costello-Batina 2015 (Algorithm 7, a = 0, b3 = 12)** — one
   branchless 12-mul formula valid for EVERY input pair in the
   prime-order subgroup, identity (0 : 1 : 0) included.  Aggregation
   inputs are vote signatures, which the CPU layer subgroup-checks on
   deserialization, so completeness holds.

Arithmetic is SIGNED-LOOSE end to end: values are congruences mod q
with limbs a hair over 13 bits (possibly negative — two's-complement
``& MASK`` and arithmetic ``>>`` keep every CIOS step algebraically
exact for signed values), ops end with one parallel carry pass (no
sequential chains, no conditional subtractions, no subtraction pads on
device — tiny XLA graphs), and canonicalization happens once on the
host after the aggregate is fetched (``from_mont_int`` reduces mod q).

Magnitude audit (worst case in point_add): REDC outputs are < 1.5q;
the deepest add/sub/x12 chain is y3 = 12*(REDC - (REDC + REDC)),
magnitude < 12*(1.5q + 3q) = 54q, fed back into mont_mul.  REDC with
R/q = 2^390/q > 500 maps products of such inputs (|ab| < 54q * 20q <
2^773) to outputs < |ab|/R + q < 3.2q — still far below R, so the
recursion is stable.  Limb magnitudes: one carry pass bounds limbs by
2^13 + (peak column >> 13); the x12 scaling peaks columns at ~2^17,
so loose limbs stay < 2^13 + 2^5.  CIOS columns accumulate at most
8 steps * 2 products * (2^13.1)^2 + residual 2^19 < 2^31.

Correctness oracle: the pure-Python backend (crypto/bls/curve.py),
tested in tests/test_tpu_bls.py.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..crypto.bls.curve import G1Point
from ..crypto.bls.fields import P as Q
from ..telemetry import spans as _spans
from ..telemetry.blsstats import BLS_COUNTS

NLIMBS = 30
LIMB_BITS = 13
MASK = (1 << LIMB_BITS) - 1
NCOLS = NLIMBS + 2  # lazy CIOS accumulator columns (carry headroom)

RADIX = 1 << (NLIMBS * LIMB_BITS)  # 2^390
R_MONT = RADIX % Q
# mu = -q^{-1} mod 2^13 (the CIOS per-limb quotient constant)
MU = (-pow(Q, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS)

_CARRY_EVERY = 8
B3 = 12  # 3*b for y^2 = x^3 + 4


def _int_to_limbs(x: int, n: int = NLIMBS) -> np.ndarray:
    out = np.zeros(n, np.int32)
    for i in range(n):
        out[i] = x & MASK
        x >>= LIMB_BITS
    assert x == 0
    return out


def limbs_to_int(limbs) -> int:
    arr = np.asarray(limbs)
    return sum(int(v) << (LIMB_BITS * i) for i, v in enumerate(arr.tolist()))


Q_LIMBS = _int_to_limbs(Q)

# Fold vectors for the two overflow columns of the CIOS accumulator:
# parallel carry passes move carries UP into columns 30/31 and never
# back down, so the final normalization folds their content into the
# low 30 limbs mod q.  Weights: col 30 = 2^390, its >>13 half and
# col 31 = 2^403, col 31's >>13 half = 2^416.
_C390 = _int_to_limbs((1 << 390) % Q)
_C403 = _int_to_limbs((1 << 403) % Q)
_C416 = _int_to_limbs((1 << 416) % Q)


def to_mont_limbs(x: int) -> np.ndarray:
    """Host: integer mod q -> Montgomery-form limb vector."""
    return _int_to_limbs((x % Q) * R_MONT % Q)


# R^2 mod q in limb form: mont_mul(a_plain, R2) = REDC(a * R^2) = a*R,
# i.e. one device multiply converts a PLAIN limb vector to Montgomery
# form — the hook that lets staging ship raw byte-split limbs (ISSUE 5)
R2_LIMBS = _int_to_limbs(RADIX * RADIX % Q)

_LIMB_SHIFTS = range(0, NLIMBS * LIMB_BITS, LIMB_BITS)


def ints_to_limbs_batch(vals: list[int]) -> np.ndarray:
    """[n] integers mod q -> [n, NLIMBS] PLAIN (non-Montgomery) limb
    rows by shifts and masks.  A vote's two coordinates are split on
    the event loop as it is staged, and at that size plain Python is
    faster than splitting through numpy's bit arrays."""
    return np.array(
        [[(v >> s) & MASK for s in _LIMB_SHIFTS] for v in vals], np.int32
    ).reshape(len(vals), NLIMBS)


def from_mont_int(limbs) -> int:
    """Host: loose Montgomery-form limbs -> canonical integer mod q."""
    return limbs_to_int(limbs) * pow(R_MONT, -1, Q) % Q


def _pass(t):
    """One parallel carry pass.  The TOP limb accumulates its incoming
    carry unmasked (values stay < 2^390-ish; masking would drop bits),
    growing by a few units per pass — harmless for int32."""
    r = jnp.concatenate([t[..., :-1] & MASK, t[..., -1:]], axis=-1)
    c = t[..., :-1] >> LIMB_BITS
    pad_cfg = [(0, 0)] * (t.ndim - 1)
    return r + jnp.pad(c, pad_cfg + [(1, 0)])[..., : t.shape[-1]]


def _window_pass(t, lo: int):
    """``_pass`` over the NCOLS columns of ``t`` from ``lo`` on, the
    columns outside left as they are."""
    top = lo + NCOLS - 1
    window = t[..., lo:top]
    pad_cfg = [(0, 0)] * (t.ndim - 1)
    carried = jnp.concatenate(
        [window & MASK, t[..., top : top + 1]], axis=-1
    ) + jnp.pad(window >> LIMB_BITS, pad_cfg + [(1, 0)])
    return jnp.concatenate([t[..., :lo], carried, t[..., top + 1 :]], axis=-1)


def mont_mul(a, b):
    """Batched Montgomery product of signed-loose inputs (|value| < ~60q,
    |limb| < 2^13.1 — see the module docstring's magnitude audit).
    Output magnitude < 3.2q, loose limbs.  a, b: int32 [..., NLIMBS].

    The CIOS accumulator does not shift: step i works on the NCOLS
    columns from i on of a 62-column array (b, q and the carry padded
    to that place), so a step is two fused operations on the TPU, not
    five, and the result is the last NCOLS columns.  The values are
    those of the shifting form, bit for bit."""
    width = NLIMBS + NCOLS
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape).reshape(-1, NLIMBS)
    b = jnp.broadcast_to(b, shape).reshape(-1, NLIMBS)
    pad_cfg = [(0, 0)]
    # a barrier, so that the compiler does not fold the 30 placements of
    # q, or of a constant b, into 30 constants, each copied to the core
    # before its step
    b, q = jax.lax.optimization_barrier((b, jnp.asarray(Q_LIMBS)))
    q0 = int(Q_LIMBS[0])
    t = jnp.zeros((a.shape[0], width), jnp.int32)
    for i in range(NLIMBS):
        ai = a[..., i : i + 1]
        low = t[..., i : i + 1] + ai * b[..., :1]
        m = ((low & MASK) * MU) & MASK
        # column i is now ≡ 0 mod 2^13: its exact carry goes to column
        # i + 1, and the column is not read again
        carry = (low + m * q0) >> LIMB_BITS
        place = (i, width - NLIMBS - i)
        t = (
            t
            + ai * jnp.pad(b, pad_cfg + [place])
            + m * jnp.pad(q, [place])
            + jnp.pad(carry, pad_cfg + [(i + 1, width - i - 2)])
        )
        if (i % _CARRY_EVERY) == _CARRY_EVERY - 1:
            t = _window_pass(t, i + 1)

    t = _pass(_pass(t[..., NLIMBS:]))
    # fold the overflow columns (carry residue parked above limb 29 by
    # the upward-only passes) back into the 30-limb window mod q —
    # dropping them loses k*2^390 ≡ k*R, i.e. an off-by-k in the value
    # domain.  Signed split keeps every product < 2^26.
    c30 = t[..., NLIMBS : NLIMBS + 1]
    c31 = t[..., NLIMBS + 1 : NLIMBS + 2]
    lo30, hi30 = c30 & MASK, c30 >> LIMB_BITS
    lo31, hi31 = c31 & MASK, c31 >> LIMB_BITS
    head = (
        t[..., :NLIMBS]
        + lo30 * jnp.asarray(_C390)
        + (hi30 + lo31) * jnp.asarray(_C403)
        + hi31 * jnp.asarray(_C416)
    )
    return _pass(_pass(head)).reshape(shape)


def madd(a, b):
    return _pass(a + b)


def msub(a, b):
    # signed-loose: negative limbs/values are fine (see module docstring)
    return _pass(a - b)


# ---- complete projective G1 (Renes-Costello-Batina 2015, Alg. 7) -----------
# Point = (X, Y, Z) loose Montgomery limb arrays; identity = (0 : 1 : 0).


def mont_mul_many(a_list, b_list):
    """The Montgomery products of several pairs of equal shape, as one
    ``mont_mul`` over the pairs stacked on a new leading axis.  The
    values are those of one ``mont_mul`` a pair, bit for bit (the
    arithmetic is elementwise over the leading axes); the program runs
    the 30 CIOS steps once, not once a pair, which divides the
    operations the device runs, and the profiler records, by the number
    of pairs."""
    return tuple(mont_mul(jnp.stack(a_list), jnp.stack(b_list)))


def point_add(p, q):
    """Complete addition: valid for every pair of subgroup points,
    including P == Q, P == -Q, and either operand at infinity.  The 12
    multiplications fall in two groups of six that depend only on what
    came before the group, so each group is one ``mont_mul_many``; the
    additions between them are stacked likewise, three at a time, each
    row the same ``madd``/``msub`` as written beside it, or a multiple by
    B3 and one pass (Montgomery form is linear, so integer scaling stays
    in form; loose limbs * 12 < 2^18, and one pass restores looseness)."""
    x1, y1, z1 = p
    x2, y2, z2 = q
    sums = [
        _pass(jnp.stack([u + v for u, v in ((x, y), (y, z), (x, z))]))
        for x, y, z in (p, q)
    ]
    t0, t1, t2, t3, t4, x3 = mont_mul_many(
        [x1, y1, z1, *sums[0]], [x2, y2, z2, *sums[1]]
    )
    # t3 = msub(t3, madd(t0, t1)); t4 = msub(t4, madd(t1, t2));
    # y3 = msub(x3, madd(t0, t2))
    inner = _pass(jnp.stack([t0 + t1, t1 + t2, t0 + t2]))
    t3, t4, y3 = _pass(jnp.stack([t3, t4, x3]) - inner)
    # x3 = madd(t0, t0); t2 = _pass(t2 * B3); y3 = _pass(y3 * B3)
    x3, t2, y3 = _pass(jnp.stack([t0 + t0, t2 * B3, y3 * B3]))
    # t0 = madd(x3, t0); z3 = madd(t1, t2); t1 = msub(t1, t2)
    t0, z3, t1 = _pass(jnp.stack([x3 + t0, t1 + t2, t1 - t2]))
    x3, t2, y3, t1, t0, z3 = mont_mul_many(
        [t4, t3, y3, t1, t0, z3], [y3, t1, t0, z3, t3, t4]
    )
    # x3 = msub(t2, x3); y3 = madd(t1, y3); z3 = madd(z3, t0)
    x3, y3, z3 = _pass(jnp.stack([t2 - x3, t1 + y3, z3 + t0]))
    return (x3, y3, z3)


def _tree_reduce(p):
    while p[0].shape[0] > 1:
        half = p[0].shape[0] // 2
        p = point_add(tuple(c[:half] for c in p), tuple(c[half:] for c in p))
    return p


def _aggregate_impl(xs, ys, zs):
    """Tree-reduce a [B, NLIMBS] batch of projective points to one point.
    B must be a power of two (callers pad with the identity).  Only the
    opt-in storm offload's weighted-signature sum (``TpuStormOffload``)
    dispatches it: a QC's votes are summed by ``TpuG1RunningSum``, or
    natively at a quorum check."""
    return tuple(c[0] for c in _tree_reduce((xs, ys, zs)))


_aggregate_kernel = partial(jax.jit, static_argnames=())(_aggregate_impl)

_DONATE: bool | None = None


def _donate_buffers() -> bool:
    """Same gate as ed25519.BatchVerifier.donate_buffers: accelerator
    backends by default, HOTSTUFF_DONATE=1/0 forces either way."""
    global _DONATE
    if _DONATE is None:
        import os

        env = os.environ.get("HOTSTUFF_DONATE", "").strip().lower()
        if env:
            _DONATE = env not in ("0", "off", "no", "false")
        else:
            _DONATE = jax.default_backend() in ("tpu", "gpu")
    return _DONATE


def projective_to_affine(x, y, z) -> G1Point:
    """The affine point of one projective (X : Y : Z) read back from
    the device, each coordinate a row of loose Montgomery limbs: one
    modular inversion on the host."""
    zi = from_mont_int(z)
    if zi == 0:
        return G1Point.identity()
    xi = from_mont_int(x)
    yi = from_mont_int(y)
    z_inv = pow(zi, Q - 2, Q)
    return G1Point(xi * z_inv % Q, yi * z_inv % Q)


def _running_add_xla(ax, ay, az, px, py, pz):
    """One incremental accumulate (ISSUE 9): the new point arrives as
    PLAIN [1, NLIMBS] limb rows (byte-split on host, no bignum work),
    Montgomery-converts in-kernel (one R^2 multiply per coordinate,
    REDC(x * R^2) = x * R), then ``point_add``s into
    the Montgomery-form accumulator.  The accumulator is ``_freshen``ed
    as it comes in: this chain is as deep as the quorum (171 sequential
    adds at 256 nodes), and
    unfreshened point_add outputs compound ~x2.5 per round until the
    CIOS columns overflow int32 (see ``_freshen``'s magnitude audit).
    Freshening the input, not the output, lets the one multiply by 1 and
    the three by R^2 run as one ``mont_mul_many``; what is stored is one
    point_add away from fresh values."""
    r2 = jnp.broadcast_to(jnp.asarray(R2_LIMBS), px.shape)
    one = jnp.broadcast_to(jnp.asarray(_ONE_MONT), ax.shape).astype(jnp.int32)
    ax, ay, az, px, py, pz = mont_mul_many(
        [ax, ay, az, px, py, pz], [one] * 3 + [r2] * 3
    )
    return point_add((ax, ay, az), (px, py, pz))


# ---- the running add as one Pallas kernel ----------------------------------
# ``_running_add_xla`` is ~200 device operations an add (three stacked
# Montgomery products of 30 CIOS steps, two fusions a step), and a QC
# is 43 adds: on the chip that is one profiler event an operation, and
# a committee whose rounds are short enough fills a traced window with
# more events than the profiler writes out in time.  The kernel below
# runs the same int32 operations, bit for bit, on one vector register
# an operand, VMEM-resident: the stacked operands of a ``mont_mul_many``
# ride the sublanes (one row a product, 8 rows), the limb columns the
# lanes (a CIOS column shift is a lane rotate of zero-padded rows), so
# an add is one custom call and a few copies.

_ROWS, _LANES = 8, 128


def _g1_const_rows() -> np.ndarray:
    """q, the three overflow-column fold vectors, Montgomery 1 and R^2,
    one a row: kernel inputs, as Pallas kernels capture no constants."""
    rows = np.zeros((_ROWS, _LANES), np.int32)
    for r, limbs in enumerate(
        (Q_LIMBS, _C390, _C403, _C416, to_mont_limbs(1), R2_LIMBS)
    ):
        rows[r, :NLIMBS] = limbs
    return rows


_G1_CONST_ROWS = _g1_const_rows()


def _lanes():
    return jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 1)


def _pass_v(t, n: int = NLIMBS):
    """``_pass`` over the first ``n`` lanes of every row (lanes from
    ``n`` on are zero and stay zero)."""
    lane = _lanes()
    carry = pltpu.roll(t >> LIMB_BITS, 1, 1)
    return jnp.where(lane < n - 1, t & MASK, t) + jnp.where(
        (lane > 0) & (lane < n), carry, 0
    )


def _window_pass_v(t, lo: int):
    """``_window_pass`` of every row."""
    lane = _lanes()
    top = lo + NCOLS - 1
    carry = pltpu.roll(t >> LIMB_BITS, 1, 1)
    return jnp.where((lane >= lo) & (lane < top), t & MASK, t) + jnp.where(
        (lane > lo) & (lane <= top), carry, 0
    )


def _mont_mul_v(a, b, const):
    """``mont_mul`` of each row of ``a`` by the same row of ``b``, limbs
    in lanes 0..NLIMBS-1: the 62-column CIOS accumulator is one row of
    lanes, and step i's placement of b and q at column i a rotate by i
    of rows whose lanes past NLIMBS are zero."""
    lane = _lanes()
    q = jnp.broadcast_to(const[0:1, :], (_ROWS, _LANES))
    q0 = int(Q_LIMBS[0])
    b0 = b[:, 0:1]
    t = jnp.zeros((_ROWS, _LANES), jnp.int32)
    for i in range(NLIMBS):
        ai = a[:, i : i + 1]
        low = t[:, i : i + 1] + ai * b0
        m = ((low & MASK) * MU) & MASK
        carry = (low + m * q0) >> LIMB_BITS
        t = (
            t
            + ai * pltpu.roll(b, i, 1)
            + m * pltpu.roll(q, i, 1)
            + jnp.where(lane == i + 1, carry, 0)
        )
        if (i % _CARRY_EVERY) == _CARRY_EVERY - 1:
            t = _window_pass_v(t, i + 1)
    # the last NCOLS columns, moved to lanes 0..NCOLS-1
    t = jnp.where(lane < NCOLS, pltpu.roll(t, _LANES - NLIMBS, 1), 0)
    t = _pass_v(_pass_v(t, NCOLS), NCOLS)
    c30, c31 = t[:, NLIMBS : NLIMBS + 1], t[:, NLIMBS + 1 : NLIMBS + 2]
    lo30, hi30 = c30 & MASK, c30 >> LIMB_BITS
    lo31, hi31 = c31 & MASK, c31 >> LIMB_BITS
    head = (
        jnp.where(lane < NLIMBS, t, 0)
        + lo30 * const[1:2, :]
        + (hi30 + lo31) * const[2:3, :]
        + hi31 * const[3:4, :]
    )
    return _pass_v(_pass_v(head))


def _stack_v(rows):
    """Rows (each [1, _LANES]) stacked into one [_ROWS, _LANES] value."""
    r = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 0)
    out = jnp.zeros((_ROWS, _LANES), jnp.int32)
    for k, v in enumerate(rows):
        out = jnp.where(r == k, jnp.broadcast_to(v, (_ROWS, _LANES)), out)
    return out


def _rows_v(m, n: int):
    return [m[k : k + 1, :] for k in range(n)]


def _running_add_rows(const, rows):
    """``_running_add_xla`` on rows: ``rows`` holds the accumulator's
    and the new point's six coordinates, one a row; the sum's three come
    back in rows 0..2 and the rows below them are zero.  Each stacked
    step below is the line of ``point_add`` it names."""
    mont = _stack_v([const[4:5, :]] * 3 + [const[5:6, :]] * 3)
    x1, y1, z1, x2, y2, z2 = _rows_v(_mont_mul_v(rows, mont, const), 6)
    sp = _rows_v(_pass_v(_stack_v([x1 + y1, y1 + z1, x1 + z1])), 3)
    sq = _rows_v(_pass_v(_stack_v([x2 + y2, y2 + z2, x2 + z2])), 3)
    t0, t1, t2, t3, t4, x3 = _rows_v(
        _mont_mul_v(_stack_v([x1, y1, z1, *sp]), _stack_v([x2, y2, z2, *sq]), const),
        6,
    )
    inner = _pass_v(_stack_v([t0 + t1, t1 + t2, t0 + t2]))
    t3, t4, y3 = _rows_v(_pass_v(_stack_v([t3, t4, x3]) - inner), 3)
    x3, t2, y3 = _rows_v(_pass_v(_stack_v([t0 + t0, t2 * B3, y3 * B3])), 3)
    t0, z3, t1 = _rows_v(_pass_v(_stack_v([x3 + t0, t1 + t2, t1 - t2])), 3)
    x3, t2, y3, t1, t0, z3 = _rows_v(
        _mont_mul_v(
            _stack_v([t4, t3, y3, t1, t0, z3]),
            _stack_v([y3, t1, t0, z3, t3, t4]),
            const,
        ),
        6,
    )
    return _pass_v(_stack_v([t2 - x3, t1 + y3, z3 + t0]))


def _running_add_kernel_body(const_ref, in_ref, out_ref):
    out_ref[...] = _running_add_rows(const_ref[...], in_ref[...])


def _running_add_pallas(ax, ay, az, px, py, pz, interpret: bool = False):
    """``_running_add_xla``'s values, bit for bit, from one Pallas
    kernel: the six [1, NLIMBS] rows go in as one zero-padded
    [_ROWS, _LANES] block and the three of the sum come out of one."""
    rows = jnp.concatenate(
        [ax, ay, az, px, py, pz, jnp.zeros((_ROWS - 6, NLIMBS), jnp.int32)]
    )
    out = pl.pallas_call(
        _running_add_kernel_body,
        out_shape=jax.ShapeDtypeStruct((_ROWS, _LANES), jnp.int32),
        interpret=interpret,
    )(jnp.asarray(_G1_CONST_ROWS), jnp.pad(rows, [(0, 0), (0, _LANES - NLIMBS)]))
    return tuple(out[k : k + 1, :NLIMBS] for k in range(3))


# ---- the fold: a buffer of staged votes in one program ----------------------
# Handing one vote to the device (its host arrays and a jitted call)
# costs the event loop ~100 times the add's ~12 us on a v5e, so the
# running sum stages each vote's affine limbs in a host buffer of
# FOLD_ROWS rows and hands the buffer over once: one ``jax.device_put``
# and one call that makes the staged rows' adds in staging order, each
# exactly the single add above.

#: staged votes a fold takes at most: a 64-node quorum (43) in one call,
#: a 256-node one (171) in three
FOLD_ROWS = 64


def _plain_points(rows):
    """[k, 2, NLIMBS] staged affine (x, y) plain limbs -> [k, 3, NLIMBS]
    projective (x : y : 1), still plain: the fold converts in-kernel."""
    z = jnp.zeros((rows.shape[0], 1, NLIMBS), jnp.int32).at[:, 0, 0].set(1)
    return jnp.concatenate([rows, z], axis=1)


def _running_fold_xla(ax, ay, az, rows, n):
    """``n`` successive ``_running_add_xla`` of the first ``n`` staged
    rows into the accumulator; the rows from ``n`` on are not read."""
    pts = _plain_points(rows)

    def step(i, acc):
        pt = pts[i]
        return _running_add_xla(*acc, pt[0:1], pt[1:2], pt[2:3])

    return jax.lax.fori_loop(0, n, step, (ax, ay, az))


def _running_fold_kernel_body(n_ref, const_ref, acc_ref, pts_ref, out_ref):
    """``_running_add_rows`` once a staged point, the first ``n_ref[0]``
    of them: the accumulator rides rows 0..2 of the carried block, the
    point's three plain coordinates rows 3..5 of its own block."""
    const = const_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 0)

    def step(i, acc):
        return _running_add_rows(const, jnp.where(row < 3, acc, pts_ref[i]))

    out_ref[...] = jax.lax.fori_loop(0, n_ref[0], step, acc_ref[...])


def _running_fold_pallas(ax, ay, az, rows, n, interpret: bool = False):
    """``_running_fold_xla``'s values, bit for bit, from one Pallas
    kernel that loops over the staged points in VMEM, the row count in
    SMEM."""
    acc = jnp.pad(
        jnp.concatenate([ax, ay, az]), [(0, _ROWS - 3), (0, _LANES - NLIMBS)]
    )
    pts = jnp.pad(
        _plain_points(rows), [(0, 0), (3, _ROWS - 6), (0, _LANES - NLIMBS)]
    )
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _running_fold_kernel_body,
        out_shape=jax.ShapeDtypeStruct((_ROWS, _LANES), jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vmem, vmem, vmem],
        out_specs=vmem,
        interpret=interpret,
    )(jnp.reshape(n, (1,)), jnp.asarray(_G1_CONST_ROWS), acc, pts)
    return tuple(out[k : k + 1, :NLIMBS] for k in range(3))


def _running_add_impl(ax, ay, az, rows, n):
    """The running sum's one program (its name is what the profiler's
    ``XLA Modules`` line shows): the fold of ``n`` staged rows, the
    Pallas kernel on a TPU, the XLA formulation elsewhere (Pallas runs
    on the CPU only interpreted)."""
    if jax.default_backend() == "tpu":
        return _running_fold_pallas(ax, ay, az, rows, n)
    return _running_fold_xla(ax, ay, az, rows, n)


_running_add_kernel = jax.jit(_running_add_impl)
# donated variant: the previous accumulator is dead the moment the new
# one exists — let XLA recycle its buffers across folds
_running_add_kernel_donated = jax.jit(
    _running_add_impl, donate_argnums=(0, 1, 2)
)


class TpuG1RunningSum:
    """Device-resident incremental G1 accumulator (ISSUE 9).

    Keeps a running Σ sig_i ON DEVICE as votes arrive, so QC formation
    at quorum is a fold of the last few votes and a readback.  ``add``
    stages a vote's limbs in a host buffer of ``FOLD_ROWS`` rows; the
    buffer is folded into the sum by one dispatch when it fills, and at
    ``snapshot()`` for what remains.  The async dispatch never blocks
    the caller; only ``snapshot()`` fences.

    Callers feed subgroup points (completeness of the addition law
    depends on it)."""

    def __init__(self):
        self._acc = None
        self._count = 0
        self._staged = np.zeros((FOLD_ROWS, 2, NLIMBS), np.int32)
        self._pending = 0
        self.reset()

    def reset(self) -> None:
        # identity (0 : 1 : 0) in Montgomery form
        self._acc = (
            jnp.zeros((1, NLIMBS), jnp.int32),
            jnp.asarray(to_mont_limbs(1), jnp.int32).reshape(1, NLIMBS),
            jnp.zeros((1, NLIMBS), jnp.int32),
        )
        self._count = 0
        self._pending = 0

    def __len__(self) -> int:
        return self._count

    def add(self, pt: G1Point) -> None:
        """Accumulate one point: staged, and the buffer folded once
        full (async dispatch)."""
        if pt.inf:
            return
        with _spans.span("agg.accumulate"):
            self._staged[self._pending] = ints_to_limbs_batch([pt.x, pt.y])
            self._pending += 1
            self._count += 1
            if self._pending == FOLD_ROWS:
                self._fold()

    def _fold(self) -> None:
        """The staged rows into the sum: one transfer, one call."""
        # the transfer may read its host array after the call returns
        # (even under ``may_alias=False``), and the buffer is staged
        # again at once: hand it a copy of its own
        rows, n = jax.device_put((self._staged.copy(), np.int32(self._pending)))
        kernel = (
            _running_add_kernel_donated
            if _donate_buffers()
            else _running_add_kernel
        )
        self._acc = kernel(*self._acc, rows, n)
        self._pending = 0
        BLS_COUNTS.add("device_calls")

    def snapshot(self) -> G1Point:
        """Fold what is staged, fence, and read the aggregate back
        (affine)."""
        with _spans.span("agg.snapshot"):
            if self._pending:
                self._fold()
            x, y, z = jax.block_until_ready(self._acc)
            return projective_to_affine(
                np.asarray(x).reshape(NLIMBS),
                np.asarray(y).reshape(NLIMBS),
                np.asarray(z).reshape(NLIMBS),
            )


def warm_g1_programs() -> dict:
    """Compile or load, before the consensus hot path, the one G1
    program a committee dispatches: the running sum's fold (the donated
    variant where ``_donate_buffers()`` picks it), whatever the
    committee's size; a fold's row count is an argument, not a shape.
    A quorum check sums its votes natively (``crypto/bls/service.py``
    ``verify_shared_msg``), so no committee dispatches the aggregation
    tree and none compiles it.  The fold sums ``G`` to ``(FOLD_ROWS +
    2)G``, a full buffer and a remainder of two, and is checked against
    the host's sum, so a wrong program stops the boot.  Returns, by
    program (``running_add``), where its first call's seconds went and
    its compile-cache hits (``FirstCallTimer``).

    The fold compiles in about a second on a v5e, near jax's threshold
    for writing the persistent compile cache, so a boot might not find
    it there and the report would count neither a hit nor a miss; the
    threshold is lifted for this one compile."""
    from . import FirstCallTimer

    g = G1Point.generator()
    points = [g]
    while len(points) < FOLD_ROWS + 2:
        points.append(points[-1] + g)
    total = g.mul(len(points) * (len(points) + 1) // 2)
    option = "jax_persistent_cache_min_compile_time_secs"
    threshold = getattr(jax.config, option)
    jax.config.update(option, 0.0)
    try:
        with FirstCallTimer() as timer:
            acc = TpuG1RunningSum()
            for pt in points:
                acc.add(pt)
            if acc.snapshot() != total:
                raise RuntimeError("G1 warmup: the running_add program is wrong")
            return {"running_add": timer.take()}
    finally:
        jax.config.update(option, threshold)


# ---- batched variable-base scalar multiplication ----------------------------
# The per-entry G1 work of distinct-digest TC verification (VERDICT r5
# item 8): r_i·H(m_i) for every entry plus the Σ r_i·sig_i aggregate.
# One MSB-first double-and-add ladder, BRANCHLESS (the conditional add
# is a jnp.where select — complete addition makes the "add" leg valid
# even when it is discarded), vectorized over the batch.  Cost:
# 2·NBITS point adds of [B]-wide batches; for 128-bit weights that is
# 256 adds regardless of batch size — the device eats the whole storm's
# ladder work in one dispatch.


SCALAR_BITS = 128  # random small-exponent weights (service.py)

_ONE_MONT = to_mont_limbs(1)


def _freshen(a):
    """Re-normalize a signed-loose value to magnitude < ~1.3q by one
    Montgomery multiply with the form of 1 (REDC divides by R, and
    R/q > 500 crushes any accumulated growth).  The SEQUENTIAL ladder
    needs this every iteration: point_add's per-op outputs can reach
    ~10q, and feeding them straight back in compounds (~x2.5 per
    round) until the CIOS columns overflow int32 — measured as wrong
    results after ~40-50 chained doublings.  The aggregation tree
    (``_aggregate_impl``) does not freshen its levels, and on XLA:CPU
    some of its sums of 40 distinct random points come out wrong; its
    one caller, the storm offload, takes a wrong sum as a failed batch
    and falls back to the host's per-item checks."""
    one = jnp.broadcast_to(jnp.asarray(_ONE_MONT), a.shape).astype(jnp.int32)
    return mont_mul(a, one)


@partial(jax.jit, static_argnames=("nbits",))
def _scalar_mult_kernel(bits, xs, ys, zs, nbits: int = SCALAR_BITS):
    """bits: [nbits, B] int32 (MSB first); points [B, NLIMBS] loose
    Montgomery projective.  Returns k_i·P_i, [B, NLIMBS] each."""
    b = xs.shape[0]
    acc = (
        jnp.zeros((b, NLIMBS), jnp.int32),
        jnp.broadcast_to(jnp.asarray(to_mont_limbs(1)), (b, NLIMBS)).astype(
            jnp.int32
        ),
        jnp.zeros((b, NLIMBS), jnp.int32),
    )

    def body(i, acc):
        acc = point_add(acc, acc)
        added = point_add(acc, (xs, ys, zs))
        take = bits[i][:, None] != 0
        acc = tuple(
            jnp.where(take, ad, ac) for ac, ad in zip(acc, added)
        )
        return tuple(_freshen(jnp.stack(acc)))

    return jax.lax.fori_loop(0, nbits, body, acc)


class TpuG1ScalarMul:
    """Batched k_i·P_i on device (the TC-storm per-entry ladders).

    The host packs scalars into MSB-first bit planes and points into
    Montgomery limbs; the device runs one branchless ladder over the
    whole batch; affine conversion happens on the host (one modular
    inversion per point, ~30 us — noise next to the ladder).
    """

    PAD_SIZES = (8, 32, 128, 512)

    def __init__(self, nbits: int = SCALAR_BITS):
        self.nbits = nbits

    def _padded(self, n: int) -> int:
        return next(
            (s for s in self.PAD_SIZES if s >= n), 1 << (n - 1).bit_length()
        )

    def mul_arrays(self, scalars: list[int], points: list[G1Point]):
        """Device ladder; returns the raw projective result as DEVICE
        arrays (x, y, z of shape [padded, NLIMBS]) — callers chain
        further device work (the storm offload feeds the wsig segment
        straight into the aggregation kernel) or convert on host."""
        n = len(points)
        assert len(scalars) == n and n > 0
        padded = self._padded(n)
        nbytes = (self.nbits + 7) // 8
        sbytes = np.zeros((padded, nbytes), np.uint8)
        packed = b"".join(k.to_bytes(nbytes, "little") for k in scalars)
        sbytes[:n] = np.frombuffer(packed, np.uint8).reshape(n, nbytes)
        lsb_first = np.unpackbits(sbytes, axis=1, bitorder="little")
        # MSB-first planes: [nbits, padded]
        bits = lsb_first[:, : self.nbits][:, ::-1].T.astype(np.int32)
        xs = np.zeros((padded, NLIMBS), np.int32)
        ys = np.zeros((padded, NLIMBS), np.int32)
        zs = np.zeros((padded, NLIMBS), np.int32)
        one = to_mont_limbs(1)
        ys[:] = one  # identity rows by default (0 : 1 : 0)
        for i, pt in enumerate(points):
            if not pt.inf:
                xs[i] = to_mont_limbs(pt.x)
                ys[i] = to_mont_limbs(pt.y)
                zs[i] = one
        return _scalar_mult_kernel(
            jnp.asarray(np.ascontiguousarray(bits)),
            jnp.asarray(xs),
            jnp.asarray(ys),
            jnp.asarray(zs),
            nbits=self.nbits,
        )

    def mul(
        self, scalars: list[int], points: list[G1Point]
    ) -> list[G1Point]:
        """[k_i·P_i] — scalars must fit in ``nbits``."""
        if not points:
            return []
        for k in scalars:
            assert 0 <= k < (1 << self.nbits)
        x, y, z = (np.asarray(a) for a in self.mul_arrays(scalars, points))
        return [
            projective_to_affine(x[i], y[i], z[i])
            for i in range(len(points))
        ]


# ---- distinct-digest storm offload ------------------------------------------
# The device side of VERDICT r5 item 8: for an all-distinct TC batch,
# every per-entry G1 ladder — signature subgroup check (order·sig),
# weighted signature (r_i·sig_i), and weighted cofactor-cleared hash
# ((r_i·h_eff)·H_base(m_i)) — runs as ONE batched device ladder of
# 3n points, followed by an on-device aggregation of the weighted
# signatures.  The host (native/bls_pairing.cpp) keeps decompression,
# hashing, and the pairing product over the returned points.


class TpuStormOffload:
    """Batched G1 ladders for distinct-digest TC verification."""

    def __init__(self):
        self._mul = TpuG1ScalarMul(nbits=256)
        # compiled (ladder_pad, agg_pad) shape pairs: batch_points
        # REFUSES un-warmed shapes (shape_ready) so a differently-sized
        # storm can never trigger a cold jit compile mid-consensus —
        # the caller falls back to the host route instead
        self._warm_shapes: set[tuple[int, int]] = set()
        self.ready = False

    def _shapes_for(self, n: int) -> tuple[int, int]:
        return self._mul._padded(3 * n), 1 << max(0, (n - 1).bit_length())

    def shape_ready(self, n: int) -> bool:
        return self._shapes_for(n) in self._warm_shapes

    def warmup(self, n: int = 171) -> None:
        """Compile/cache the ladder + aggregation shapes for an n-entry
        storm (3n-point ladder batch) before the consensus hot path.
        Call once per storm size of interest; other sizes fall back to
        the host route rather than compiling under a round timer."""
        from ..crypto.bls.curve import G1Point

        g = G1Point.generator()
        ladder_pad, agg_pad = self._shapes_for(n)
        self._mul.mul([1] * ladder_pad, [g] * ladder_pad)  # the storm shape
        # aggregation shape for the wsig segment
        xs = np.zeros((agg_pad, NLIMBS), np.int32)
        ys = np.tile(to_mont_limbs(1), (agg_pad, 1)).astype(np.int32)
        zs = np.zeros((agg_pad, NLIMBS), np.int32)
        _aggregate_kernel(jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(zs))
        self._warm_shapes.add((ladder_pad, agg_pad))
        self.ready = True

    def batch_points(self, weights: list[int], bases, sigs):
        """(whm_points, agg_point, subgroup_ok) for the native pairing
        product.  ``bases`` are PRE-cofactor hash points, ``sigs``
        on-curve (subgroup membership checked HERE via the order
        ladder).  whm_i = (w_i·h_eff)·base_i; agg = Σ w_i·sig_i."""
        from ..crypto.bls.curve import H1
        from ..crypto.bls.fields import R as ORDER

        n = len(bases)
        assert len(weights) == n and len(sigs) == n
        scalars = (
            [w * H1 for w in weights] + list(weights) + [ORDER] * n
        )
        x, y, z = self._mul.mul_arrays(scalars, list(bases) + list(sigs) * 2)
        x, y, z = np.asarray(x), np.asarray(y), np.asarray(z)
        # subgroup: order·sig must be the identity (z == 0 mod q).
        # The G1 cofactor has SMALL prime factors, so a small-order
        # component must be caught per signature — aggregate-only
        # checking is unsound here (see native verify_batch's comment).
        subgroup_ok = all(
            from_mont_int(z[2 * n + i]) == 0 for i in range(n)
        )
        whm = [
            projective_to_affine(x[i], y[i], z[i])
            for i in range(n)
        ]
        # aggregate the wsig segment on device; the pad MUST come from
        # _shapes_for — the shape_ready gate compares against it, and an
        # independently computed pad could drift and defeat the
        # no-cold-compile-mid-consensus guarantee
        _, agg_pad = self._shapes_for(n)
        xs = np.zeros((agg_pad, NLIMBS), np.int32)
        ys = np.tile(to_mont_limbs(1), (agg_pad, 1)).astype(np.int32)
        zs = np.zeros((agg_pad, NLIMBS), np.int32)
        xs[:n], ys[:n], zs[:n] = x[n : 2 * n], y[n : 2 * n], z[n : 2 * n]
        ax, ay, az = _aggregate_kernel(
            jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(zs)
        )
        agg = projective_to_affine(
            np.asarray(ax).reshape(NLIMBS),
            np.asarray(ay).reshape(NLIMBS),
            np.asarray(az).reshape(NLIMBS),
        )
        return whm, agg, subgroup_ok
