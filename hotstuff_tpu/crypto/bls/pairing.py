"""Optimal ate pairing on BLS12-381 — performance-structured.

The round-1 version was a transparently-correct textbook loop (affine
arithmetic lifted into Fq12, final exponentiation by the full 4314-bit
integer) at ~2.6 s per pairing equality — unusable on a live consensus
path.  This rewrite keeps the identical tower and conventions but uses
the standard performance structure (the same shape every production
BLS12-381 library uses — e.g. the zkcrypto/blst Miller loop):

- **Miller loop on the twist**: the running point stays in affine Fq2
  coordinates on E'; each step's line function is evaluated directly in
  the sparse form ``l·w³ = (λ·xT − yT) + (−λ·xP)·v + yP·(v·w)`` (three
  non-zero Fq2 slots out of six), multiplied into the accumulator with
  an 18-mul sparse product instead of a full 54-mul Fq12 multiply.  The
  stray ``w³`` factor per line is legitimate: ``w^((p¹²−1)/r) = 1``
  (checked numerically), so the final exponentiation kills every
  monomial in ``w``.
- **Final exponentiation by the BLS12 addition chain**: easy part
  ``f^((p⁶−1)(p²+1))`` via one conjugate, one inverse and one double
  Frobenius; hard part via the standard parameter chain
  ``(x−1)²·(x+p)·(x²+p²−1) + 3  =  3·(p⁴−p²+1)/r``
  (verified exactly), i.e. five exponentiations by the 64-bit |x|
  instead of one by a 4314-bit integer.  After the easy part the value
  lies in the cyclotomic subgroup, where inversion is conjugation —
  the negative parameter costs nothing.

The computed value is therefore ``e(P,Q)³`` — a fixed cube of the ate
pairing.  Since gcd(3, r) = 1, g ↦ g³ is a bijection of the r-order
target group: the cube is itself a non-degenerate bilinear pairing, and
every protocol use (equality of pairings, bilinearity) is unaffected.
Tests pin this against the retained textbook oracle
(``pairing_textbook(P,Q)³ == pairing(P,Q)``).

Measured (this host): pairing equality 2.6 s → ~40 ms (one Miller loop
~12 ms; the shared final exponentiation ~15 ms; G1/G2 decompression and
hash-to-curve account for the rest of a signature verify).

Reference boundary this backend slots behind: the SignatureService /
verify path of crypto/src/lib.rs:186-257 (BASELINE config 5).
"""

from __future__ import annotations

from ...telemetry.blsstats import BLS_COUNTS
from .curve import G1Point, G2Point
from .fields import P, R, X, Fq2, Fq6, Fq12

# -- sparse Fq12 accumulation ------------------------------------------------


def _mul_sparse_014(f: Fq12, a: Fq2, b: Fq2, c: Fq2) -> Fq12:
    """f · (a + b·v + c·v·w)  — the line-evaluation shape.

    With f = f0 + f1·w (f_i in Fq6) and s = s0 + s1·w where s0 = a + b·v
    and s1 = c·v:  f·s = (f0·s0 + f1·s1·v) + (f0·s1 + f1·s0)·w.
    Each sparse Fq6 product costs 6 (two-term) or 3 (one-term) Fq2 muls:
    18 total vs 54 for a generic Fq12 multiply.
    """
    f00, f01, f02 = f.c0.c0, f.c0.c1, f.c0.c2
    f10, f11, f12 = f.c1.c0, f.c1.c1, f.c1.c2

    def mul_ab(x0: Fq2, x1: Fq2, x2: Fq2) -> tuple[Fq2, Fq2, Fq2]:
        # (x0 + x1 v + x2 v²)(a + b v), v³ = u+1
        return (
            x0 * a + (x2 * b).mul_by_nonresidue(),
            x0 * b + x1 * a,
            x1 * b + x2 * a,
        )

    def mul_c(x0: Fq2, x1: Fq2, x2: Fq2) -> tuple[Fq2, Fq2, Fq2]:
        # (x0 + x1 v + x2 v²)(c v)
        return ((x2 * c).mul_by_nonresidue(), x0 * c, x1 * c)

    p00, p01, p02 = mul_ab(f00, f01, f02)  # f0·s0
    q0, q1, q2 = mul_c(f10, f11, f12)  # f1·s1
    # f1·s1·v : rotate with nonresidue
    r0, r1, r2 = q2.mul_by_nonresidue(), q0, q1
    c0 = Fq6(p00 + r0, p01 + r1, p02 + r2)

    s00, s01, s02 = mul_c(f00, f01, f02)  # f0·s1
    t0, t1, t2 = mul_ab(f10, f11, f12)  # f1·s0
    c1 = Fq6(s00 + t0, s01 + t1, s02 + t2)
    return Fq12(c0, c1)


# -- Miller loop -------------------------------------------------------------

_X_ABS_BITS = bin(abs(X))[3:]  # MSB-first, leading 1 skipped


def miller_loop(p: G1Point, q: G2Point) -> Fq12:
    """Accumulated (scaled) Miller value f_{|x|,Q}(P).

    The running point T stays in Jacobian coordinates on the twist
    (x = X/Z², y = Y/Z³) so the loop does ZERO field inversions — a
    381-bit modular inversion costs ~335 µs in Python (measured), which
    at one per step was over half the loop.  Each line is scaled by its
    projective denominator, an Fq2 factor; like the w³ embedding factor,
    anything in a proper subfield dies under the final exponentiation.

    Tangent at T, evaluated at P, scaled by 2YZ³:
      a = 3X³ − 2Y²,  b = −3X²Z²·xP,  c = 2YZ³·yP
    Chord through T and affine Q, scaled by Z³·D (D = xq·Z² − X):
      N = yq·Z³ − Y
      a = N·X − Y·D,  b = −N·Z²·xP,  c = Z³·D·yP
    """
    if p.inf or q.inf:
        return Fq12.ONE
    from .curve import _FQ2_OPS, _jac_add, _jac_double

    xp, yp = p.x, p.y
    xq, yq = q.x, q.y  # Fq2, twist affine
    q_jac = (xq, yq, Fq2.ONE)
    T = q_jac
    f = Fq12.ONE
    for bit in _X_ABS_BITS:
        Xt, Yt, Zt = T
        X2 = Xt.square()
        Y2 = Yt.square()
        Z2 = Zt.square()
        Z3 = Zt * Z2
        line_a = (Xt * X2).mul_int(3) - Y2 - Y2
        line_b = -((X2.mul_int(3) * Z2).mul_int(xp))
        line_c = ((Yt + Yt) * Z3).mul_int(yp)
        f = f.square()
        f = _mul_sparse_014(f, line_a, line_b, line_c)
        T = _jac_double(T, _FQ2_OPS)
        if bit == "1":
            Xt, Yt, Zt = T
            Z2 = Zt.square()
            Z3 = Zt * Z2
            n = yq * Z3 - Yt
            d = xq * Z2 - Xt
            line_a = n * Xt - Yt * d
            line_b = -((n * Z2).mul_int(xp))
            line_c = (Z3 * d).mul_int(yp)
            f = _mul_sparse_014(f, line_a, line_b, line_c)
            T = _jac_add(T, q_jac, _FQ2_OPS)
    if X < 0:
        f = f.conjugate()  # f^(p^6) inverts the exponent cheaply
    return f


# -- final exponentiation ----------------------------------------------------


def _pow_abs_x(f: Fq12) -> Fq12:
    """f^|x| by square-and-multiply (|x| is 64 bits, weight 6).  Callers
    only pass cyclotomic elements (post-easy-part), so the chain runs on
    Granger-Scott squarings."""
    result = f
    for bit in _X_ABS_BITS:
        result = result.cyclotomic_square()
        if bit == "1":
            result = result * f
    return result


def _pow_x(f: Fq12) -> Fq12:
    """f^x for the (negative) BLS parameter; f must be cyclotomic so
    that conjugation is inversion."""
    out = _pow_abs_x(f)
    return out.conjugate() if X < 0 else out


def final_exponentiation(f: Fq12) -> Fq12:
    """f^(3·(p¹²−1)/r) via easy part + the BLS12 parameter chain.

    Hard-part identity (verified exactly against the integers):
    (x−1)²·(x+p)·(x²+p²−1) + 3 = 3·(p⁴−p²+1)/r.
    """
    # easy part: f^((p^6−1)(p^2+1)) — lands in the cyclotomic subgroup
    t = f.conjugate() * f.inverse()  # f^(p^6 − 1)
    f = t.frobenius(2) * t  # ^(p^2 + 1)
    # hard part: ^((x−1)²(x+p)(x²+p²−1)) · f³
    t1 = _pow_x(f) * f.conjugate()  # f^(x−1)
    t1 = _pow_x(t1) * t1.conjugate()  # ^(x−1)²
    t2 = _pow_x(t1) * t1.frobenius(1)  # ^(x+p)
    t3 = _pow_x(_pow_x(t2))  # ^x²
    t3 = t3 * t2.frobenius(2) * t2.conjugate()  # ^(x²+p²−1)
    return t3 * f.square() * f  # · f³


def pairing(p: G1Point, q: G2Point) -> Fq12:
    """e(P, Q)³: a fixed cube of the optimal ate pairing — bilinear and
    non-degenerate (3 is invertible mod r)."""
    return final_exponentiation(miller_loop(p, q))


def pairings_equal(
    p1: G1Point, q1: G2Point, p2: G1Point, q2: G2Point
) -> bool:
    """e(P1, Q1) == e(P2, Q2) via one product: e(P1,Q1)·e(-P2,Q2) == 1 —
    shares the final exponentiation between the two Miller loops (the
    fixed cube preserves the equality: g³ = 1 ⇔ g = 1 in the r-group)."""
    BLS_COUNTS.add("pairings")
    f = miller_loop(p1, q1) * miller_loop(-p2, q2)
    return final_exponentiation(f) == Fq12.ONE


# -- textbook oracle (round-1 implementation, kept for tests) ----------------


def _fq2_to_fq12(a: Fq2) -> Fq12:
    return Fq12(Fq6(a, Fq2.ZERO, Fq2.ZERO), Fq6.ZERO)


def _fq_to_fq12(a: int) -> Fq12:
    return _fq2_to_fq12(Fq2(a, 0))


_W = Fq12(Fq6.ZERO, Fq6.ONE)
_W2_INV = (_W * _W).inverse()
_W3_INV = (_W * _W * _W).inverse()


def _miller_loop_textbook(p: G1Point, q: G2Point) -> Fq12:
    """Round-1 textbook loop: affine arithmetic lifted into Fq12 with the
    exact (unscaled) line values — the correctness oracle for tests."""
    if p.inf or q.inf:
        return Fq12.ONE

    def line(px, py, qx, qy, rx, ry):
        if px == qx and py == qy:
            lam = _fq_to_fq12(3) * px * px * (_fq_to_fq12(2) * py).inverse()
        elif px == qx:
            return rx - px
        else:
            lam = (qy - py) * (qx - px).inverse()
        return ry - py - lam * (rx - px)

    px, py = _fq_to_fq12(p.x), _fq_to_fq12(p.y)
    qx = _fq2_to_fq12(q.x) * _W2_INV
    qy = _fq2_to_fq12(q.y) * _W3_INV
    f = Fq12.ONE
    rx, ry = qx, qy
    for bit in _X_ABS_BITS:
        f = f * f * line(rx, ry, rx, ry, px, py)
        lam = _fq_to_fq12(3) * rx * rx * (_fq_to_fq12(2) * ry).inverse()
        new_x = lam * lam - rx - rx
        new_y = lam * (rx - new_x) - ry
        rx, ry = new_x, new_y
        if bit == "1":
            f = f * line(rx, ry, qx, qy, px, py)
            if rx == qx and ry == qy:
                lam = _fq_to_fq12(3) * rx * rx * (_fq_to_fq12(2) * ry).inverse()
            else:
                lam = (qy - ry) * (qx - rx).inverse()
            new_x = lam * lam - rx - qx
            new_y = lam * (rx - new_x) - ry
            rx, ry = new_x, new_y
    if X < 0:
        f = f.conjugate()
    return f


def pairing_textbook(p: G1Point, q: G2Point) -> Fq12:
    """Exact e(P, Q) by the round-1 method (slow; tests only)."""
    return _miller_loop_textbook(p, q).pow((P**12 - 1) // R)
