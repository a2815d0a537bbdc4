// BLS12-381 signature verification — native path.
//
// A direct C++ port of the framework's OWN Python implementation
// (hotstuff_tpu/crypto/bls/{fields,curve,pairing}.py — which is the
// correctness oracle it is tested against): same tower (Fq2 = Fq[u]/(u²+1),
// Fq6 = Fq2[v]/(v³−(u+1)), Fq12 = Fq6[w]/(w²−v)), same Jacobian-twist
// Miller loop with w³-scaled lines, same easy-part + BLS12 parameter-chain
// final exponentiation (the computed value is e(P,Q)³ — a fixed cube,
// bilinear and non-degenerate; only equalities are consumed).  Fq is
// 6×64-bit Montgomery (CIOS with unsigned __int128).
//
// Purpose: the pure-Python pairing equality costs ~40 ms — fine for one
// aggregate check per certificate, unusable for per-message
// authentication (timeout floods).  This path brings verify-one to
// ~1-2 ms.  Exposed via ctypes (hotstuff_tpu/crypto/bls/native.py) with
// graceful fallback to the Python backend.
//
// Reference boundary being accelerated: the SignatureService / verify
// path of the reference's crypto crate (crypto/src/lib.rs:186-257),
// BASELINE config 5.

#include <chrono>
#include <memory>
#include <cstdint>
#include <cstring>
#include <mutex>
#include <string>
#include <unordered_map>
#include <vector>

#include "bls_constants.h"

namespace {

constexpr int L = 6;  // 64-bit limbs in Fq

// ---------------------------------------------------------------- fp core
struct Fp {
  uint64_t v[L];
};

inline bool fp_is_zero(const Fp &a) {
  uint64_t acc = 0;
  for (int i = 0; i < L; i++) acc |= a.v[i];
  return acc == 0;
}

inline bool fp_eq(const Fp &a, const Fp &b) {
  uint64_t acc = 0;
  for (int i = 0; i < L; i++) acc |= a.v[i] ^ b.v[i];
  return acc == 0;
}

// a >= b on raw limb values
inline bool fp_geq(const uint64_t *a, const uint64_t *b) {
  for (int i = L - 1; i >= 0; i--) {
    if (a[i] > b[i]) return true;
    if (a[i] < b[i]) return false;
  }
  return true;  // equal
}

inline void fp_sub_raw(uint64_t *r, const uint64_t *a, const uint64_t *b) {
  unsigned __int128 borrow = 0;
  for (int i = 0; i < L; i++) {
    unsigned __int128 d =
        (unsigned __int128)a[i] - b[i] - (uint64_t)borrow;
    r[i] = (uint64_t)d;
    borrow = (d >> 64) ? 1 : 0;
  }
}

inline void fp_add(Fp &r, const Fp &a, const Fp &b) {
  unsigned __int128 carry = 0;
  for (int i = 0; i < L; i++) {
    unsigned __int128 s = (unsigned __int128)a.v[i] + b.v[i] + (uint64_t)carry;
    r.v[i] = (uint64_t)s;
    carry = s >> 64;
  }
  if (carry || fp_geq(r.v, BLS_Q)) fp_sub_raw(r.v, r.v, BLS_Q);
}

inline void fp_sub(Fp &r, const Fp &a, const Fp &b) {
  unsigned __int128 borrow = 0;
  uint64_t t[L];
  for (int i = 0; i < L; i++) {
    unsigned __int128 d =
        (unsigned __int128)a.v[i] - b.v[i] - (uint64_t)borrow;
    t[i] = (uint64_t)d;
    borrow = (d >> 64) ? 1 : 0;
  }
  if (borrow) {
    unsigned __int128 carry = 0;
    for (int i = 0; i < L; i++) {
      unsigned __int128 s = (unsigned __int128)t[i] + BLS_Q[i] + (uint64_t)carry;
      t[i] = (uint64_t)s;
      carry = s >> 64;
    }
  }
  std::memcpy(r.v, t, sizeof t);
}

inline void fp_neg(Fp &r, const Fp &a) {
  if (fp_is_zero(a)) {
    r = a;
    return;
  }
  fp_sub_raw(r.v, BLS_Q, a.v);
}

// Montgomery CIOS multiply: r = a*b*R^{-1} mod q
inline void fp_mul(Fp &r, const Fp &a, const Fp &b) {
  uint64_t t[L + 1] = {0};
  for (int i = 0; i < L; i++) {
    // t += a[i] * b
    unsigned __int128 carry = 0;
    for (int j = 0; j < L; j++) {
      unsigned __int128 s =
          (unsigned __int128)a.v[i] * b.v[j] + t[j] + (uint64_t)carry;
      t[j] = (uint64_t)s;
      carry = s >> 64;
    }
    uint64_t t_extra = (uint64_t)carry;
    // m = t[0] * n0 mod 2^64 ; t += m*q; t >>= 64
    uint64_t m = t[0] * BLS_N0;
    carry = 0;
    for (int j = 0; j < L; j++) {
      unsigned __int128 s =
          (unsigned __int128)m * BLS_Q[j] + t[j] + (uint64_t)carry;
      t[j] = (uint64_t)s;
      carry = s >> 64;
    }
    unsigned __int128 s = (unsigned __int128)t[L] + t_extra + (uint64_t)carry;
    // shift down one limb
    for (int j = 0; j < L - 1; j++) t[j] = t[j + 1];
    t[L - 1] = (uint64_t)s;
    t[L] = (uint64_t)(s >> 64);
  }
  // t[L] is 0 or 1; conditional subtract
  if (t[L] || fp_geq(t, BLS_Q)) fp_sub_raw(t, t, BLS_Q);
  std::memcpy(r.v, t, sizeof(uint64_t) * L);
}

inline void fp_sqr(Fp &r, const Fp &a) { fp_mul(r, a, a); }

inline void fp_set(Fp &r, const uint64_t *src) {
  std::memcpy(r.v, src, sizeof(uint64_t) * L);
}

inline Fp fp_one() {
  Fp r;
  fp_set(r, BLS_ONE_M);
  return r;
}

inline Fp fp_zero() {
  Fp r{};
  return r;
}

// pow by a little-endian limb exponent (not Montgomery exponent)
inline void fp_pow(Fp &r, const Fp &base, const uint64_t *e, int elimbs) {
  Fp acc = fp_one();
  Fp b = base;
  bool started = false;
  // MSB-first over all bits
  for (int i = elimbs - 1; i >= 0; i--) {
    for (int bit = 63; bit >= 0; bit--) {
      if (started) fp_sqr(acc, acc);
      if ((e[i] >> bit) & 1) {
        if (started)
          fp_mul(acc, acc, b);
        else {
          acc = b;
          started = true;
        }
      }
    }
  }
  r = started ? acc : fp_one();
}

inline void fp_inv(Fp &r, const Fp &a) { fp_pow(r, a, BLS_Q_M2, L); }

// canonical (non-Montgomery) value, for serialization / comparisons
inline void fp_from_mont(uint64_t out[L], const Fp &a) {
  // multiply by 1 (non-Montgomery) via CIOS == divide by R
  Fp one_raw{};
  one_raw.v[0] = 1;
  Fp t;
  fp_mul(t, a, one_raw);
  std::memcpy(out, t.v, sizeof(uint64_t) * L);
}

inline void fp_to_mont(Fp &r, const uint64_t raw[L]) {
  Fp a;
  std::memcpy(a.v, raw, sizeof(uint64_t) * L);
  Fp r2;
  fp_set(r2, BLS_R2);
  fp_mul(r, a, r2);
}

// 48-byte big-endian -> raw limbs; returns false if >= q
inline bool fp_raw_from_be48(uint64_t out[L], const uint8_t *be) {
  for (int i = 0; i < L; i++) {
    uint64_t w = 0;
    for (int j = 0; j < 8; j++) w = (w << 8) | be[(L - 1 - i) * 8 + j];
    out[i] = w;
  }
  return !fp_geq(out, BLS_Q);
}

// canonical value comparison with (q-1)/2 ("is y lexicographically large")
inline bool fp_canon_gt_half(const Fp &a) {
  uint64_t raw[L];
  fp_from_mont(raw, a);
  // raw > (q-1)/2  <=>  raw >= (q-1)/2 + 1
  uint64_t half[L];
  std::memcpy(half, BLS_QM1_2, sizeof half);
  // compare raw > half
  for (int i = L - 1; i >= 0; i--) {
    if (raw[i] > half[i]) return true;
    if (raw[i] < half[i]) return false;
  }
  return false;
}

// ---------------------------------------------------------------- fp2
struct Fp2 {
  Fp c0, c1;
};

inline Fp2 fp2_zero() { return {fp_zero(), fp_zero()}; }
inline Fp2 fp2_one() { return {fp_one(), fp_zero()}; }

inline bool fp2_is_zero(const Fp2 &a) {
  return fp_is_zero(a.c0) && fp_is_zero(a.c1);
}

inline bool fp2_eq(const Fp2 &a, const Fp2 &b) {
  return fp_eq(a.c0, b.c0) && fp_eq(a.c1, b.c1);
}

inline void fp2_add(Fp2 &r, const Fp2 &a, const Fp2 &b) {
  fp_add(r.c0, a.c0, b.c0);
  fp_add(r.c1, a.c1, b.c1);
}

inline void fp2_sub(Fp2 &r, const Fp2 &a, const Fp2 &b) {
  fp_sub(r.c0, a.c0, b.c0);
  fp_sub(r.c1, a.c1, b.c1);
}

inline void fp2_neg(Fp2 &r, const Fp2 &a) {
  fp_neg(r.c0, a.c0);
  fp_neg(r.c1, a.c1);
}

inline void fp2_mul(Fp2 &r, const Fp2 &a, const Fp2 &b) {
  // Karatsuba: (a0+a1u)(b0+b1u) = a0b0 - a1b1 + ((a0+a1)(b0+b1) - a0b0 - a1b1)u
  Fp t0, t1, t2, s0, s1;
  fp_mul(t0, a.c0, b.c0);
  fp_mul(t1, a.c1, b.c1);
  fp_add(s0, a.c0, a.c1);
  fp_add(s1, b.c0, b.c1);
  fp_mul(t2, s0, s1);
  fp_sub(r.c0, t0, t1);
  fp_sub(t2, t2, t0);
  fp_sub(r.c1, t2, t1);
}

inline void fp2_sqr(Fp2 &r, const Fp2 &a) {
  // (a+bu)^2 = (a+b)(a-b) + 2ab u
  Fp s, d, m;
  fp_add(s, a.c0, a.c1);
  fp_sub(d, a.c0, a.c1);
  fp_mul(m, a.c0, a.c1);
  fp_mul(r.c0, s, d);
  fp_add(r.c1, m, m);
}

inline void fp2_conj(Fp2 &r, const Fp2 &a) {
  r.c0 = a.c0;
  fp_neg(r.c1, a.c1);
}

inline void fp2_mul_nonres(Fp2 &r, const Fp2 &a) {
  // * (u + 1): (c0 - c1) + (c0 + c1) u
  Fp t0, t1;
  fp_sub(t0, a.c0, a.c1);
  fp_add(t1, a.c0, a.c1);
  r.c0 = t0;
  r.c1 = t1;
}

inline void fp2_inv(Fp2 &r, const Fp2 &a) {
  // 1/(a+bu) = (a - bu)/(a^2 + b^2)
  Fp n, t, inv;
  fp_sqr(n, a.c0);
  fp_sqr(t, a.c1);
  fp_add(n, n, t);
  fp_inv(inv, n);
  fp_mul(r.c0, a.c0, inv);
  Fp negb;
  fp_neg(negb, a.c1);
  fp_mul(r.c1, negb, inv);
}

inline void fp2_mul_fp(Fp2 &r, const Fp2 &a, const Fp &k) {
  fp_mul(r.c0, a.c0, k);
  fp_mul(r.c1, a.c1, k);
}

inline void fp2_pow(Fp2 &r, const Fp2 &base, const uint64_t *e, int elimbs) {
  Fp2 acc = fp2_one();
  Fp2 b = base;
  bool started = false;
  for (int i = elimbs - 1; i >= 0; i--) {
    for (int bit = 63; bit >= 0; bit--) {
      if (started) fp2_sqr(acc, acc);
      if ((e[i] >> bit) & 1) {
        if (started)
          fp2_mul(acc, acc, b);
        else {
          acc = b;
          started = true;
        }
      }
    }
  }
  r = started ? acc : fp2_one();
}

// sqrt in Fq2 (Adj/Rodríguez-Henríquez, q ≡ 3 mod 4) — port of
// fields.py::Fq2.sqrt.  Returns false if no root.
inline bool fp2_sqrt(Fp2 &r, const Fp2 &a) {
  if (fp2_is_zero(a)) {
    r = fp2_zero();
    return true;
  }
  Fp2 a1, alpha, x0;
  fp2_pow(a1, a, BLS_QM3_4, L);
  fp2_sqr(alpha, a1);
  fp2_mul(alpha, alpha, a);
  fp2_mul(x0, a1, a);
  Fp2 neg_one = fp2_one();
  fp_neg(neg_one.c0, neg_one.c0);
  if (fp2_eq(alpha, neg_one)) {
    // (-x0.c1, x0.c0)
    Fp t;
    fp_neg(t, x0.c1);
    r.c1 = x0.c0;
    r.c0 = t;
    return true;
  }
  Fp2 b, cand, chk;
  fp2_add(b, alpha, fp2_one());
  fp2_pow(b, b, BLS_QM1_2_FULL, L);
  fp2_mul(cand, b, x0);
  fp2_sqr(chk, cand);
  if (!fp2_eq(chk, a)) return false;
  r = cand;
  return true;
}

// ---------------------------------------------------------------- fp6
struct Fp6 {
  Fp2 c0, c1, c2;
};

inline Fp6 fp6_zero() { return {fp2_zero(), fp2_zero(), fp2_zero()}; }
inline Fp6 fp6_one() { return {fp2_one(), fp2_zero(), fp2_zero()}; }

inline bool fp6_eq(const Fp6 &a, const Fp6 &b) {
  return fp2_eq(a.c0, b.c0) && fp2_eq(a.c1, b.c1) && fp2_eq(a.c2, b.c2);
}

inline void fp6_add(Fp6 &r, const Fp6 &a, const Fp6 &b) {
  fp2_add(r.c0, a.c0, b.c0);
  fp2_add(r.c1, a.c1, b.c1);
  fp2_add(r.c2, a.c2, b.c2);
}

inline void fp6_sub(Fp6 &r, const Fp6 &a, const Fp6 &b) {
  fp2_sub(r.c0, a.c0, b.c0);
  fp2_sub(r.c1, a.c1, b.c1);
  fp2_sub(r.c2, a.c2, b.c2);
}

inline void fp6_neg(Fp6 &r, const Fp6 &a) {
  fp2_neg(r.c0, a.c0);
  fp2_neg(r.c1, a.c1);
  fp2_neg(r.c2, a.c2);
}

inline void fp6_mul(Fp6 &r, const Fp6 &a, const Fp6 &b) {
  // port of fields.py::Fq6.__mul__ (Karatsuba-style with nonresidue folds)
  Fp2 t0, t1, t2, s, u, c0, c1, c2;
  fp2_mul(t0, a.c0, b.c0);
  fp2_mul(t1, a.c1, b.c1);
  fp2_mul(t2, a.c2, b.c2);
  // c0 = ((a1 + a2)(b1 + b2) - t1 - t2) * nonres + t0
  fp2_add(s, a.c1, a.c2);
  fp2_add(u, b.c1, b.c2);
  fp2_mul(c0, s, u);
  fp2_sub(c0, c0, t1);
  fp2_sub(c0, c0, t2);
  fp2_mul_nonres(c0, c0);
  fp2_add(c0, c0, t0);
  // c1 = (a0 + a1)(b0 + b1) - t0 - t1 + t2 * nonres
  fp2_add(s, a.c0, a.c1);
  fp2_add(u, b.c0, b.c1);
  fp2_mul(c1, s, u);
  fp2_sub(c1, c1, t0);
  fp2_sub(c1, c1, t1);
  Fp2 t2n;
  fp2_mul_nonres(t2n, t2);
  fp2_add(c1, c1, t2n);
  // c2 = (a0 + a2)(b0 + b2) - t0 - t2 + t1
  fp2_add(s, a.c0, a.c2);
  fp2_add(u, b.c0, b.c2);
  fp2_mul(c2, s, u);
  fp2_sub(c2, c2, t0);
  fp2_sub(c2, c2, t2);
  fp2_add(c2, c2, t1);
  r.c0 = c0;
  r.c1 = c1;
  r.c2 = c2;
}

inline void fp6_mul_nonres(Fp6 &r, const Fp6 &a) {
  // * v : (c2 * (u+1), c0, c1)
  Fp2 t;
  fp2_mul_nonres(t, a.c2);
  Fp2 old0 = a.c0, old1 = a.c1;
  r.c0 = t;
  r.c1 = old0;
  r.c2 = old1;
}

inline void fp6_inv(Fp6 &r, const Fp6 &x) {
  // port of fields.py::Fq6.inverse
  Fp2 a = x.c0, b = x.c1, c = x.c2;
  Fp2 t0, t1, t2, bc, cs, as_, denom, tmp;
  fp2_sqr(t0, a);
  fp2_mul(bc, b, c);
  fp2_mul_nonres(tmp, bc);
  fp2_sub(t0, t0, tmp);  // t0 = a^2 - (b c) nonres
  fp2_sqr(cs, c);
  fp2_mul_nonres(t1, cs);
  fp2_mul(tmp, a, b);
  fp2_sub(t1, t1, tmp);  // t1 = c^2 nonres - a b
  fp2_sqr(t2, b);
  fp2_mul(as_, a, c);
  fp2_sub(t2, t2, as_);  // t2 = b^2 - a c
  // denom = a t0 + (c t1 + b t2) nonres
  Fp2 u, v;
  fp2_mul(u, c, t1);
  fp2_mul(v, b, t2);
  fp2_add(u, u, v);
  fp2_mul_nonres(u, u);
  fp2_mul(v, a, t0);
  fp2_add(denom, v, u);
  Fp2 dinv;
  fp2_inv(dinv, denom);
  fp2_mul(r.c0, t0, dinv);
  fp2_mul(r.c1, t1, dinv);
  fp2_mul(r.c2, t2, dinv);
}

// ---------------------------------------------------------------- fp12
struct Fp12 {
  Fp6 c0, c1;
};

inline Fp12 fp12_one() { return {fp6_one(), fp6_zero()}; }

inline bool fp12_eq(const Fp12 &a, const Fp12 &b) {
  return fp6_eq(a.c0, b.c0) && fp6_eq(a.c1, b.c1);
}

inline void fp12_mul(Fp12 &r, const Fp12 &a, const Fp12 &b) {
  Fp6 t0, t1, s, u, c0, c1;
  fp6_mul(t0, a.c0, b.c0);
  fp6_mul(t1, a.c1, b.c1);
  Fp6 t1n;
  fp6_mul_nonres(t1n, t1);
  fp6_add(c0, t0, t1n);
  fp6_add(s, a.c0, a.c1);
  fp6_add(u, b.c0, b.c1);
  fp6_mul(c1, s, u);
  fp6_sub(c1, c1, t0);
  fp6_sub(c1, c1, t1);
  r.c0 = c0;
  r.c1 = c1;
}

inline void fp12_sqr(Fp12 &r, const Fp12 &a) {
  // complex squaring (port of fields.py::Fq12.square)
  Fp6 t, m, s, u;
  fp6_mul(t, a.c0, a.c1);
  fp6_add(s, a.c0, a.c1);
  Fp6 c1n;
  fp6_mul_nonres(c1n, a.c1);
  fp6_add(u, a.c0, c1n);
  fp6_mul(m, s, u);
  fp6_sub(m, m, t);
  Fp6 tn;
  fp6_mul_nonres(tn, t);
  fp6_sub(r.c0, m, tn);
  fp6_add(r.c1, t, t);
}

inline void fp12_conj(Fp12 &r, const Fp12 &a) {
  r.c0 = a.c0;
  fp6_neg(r.c1, a.c1);
}

inline void fp12_inv(Fp12 &r, const Fp12 &a) {
  // port of fields.py::Fq12.inverse
  Fp6 t0, t1, denom, dinv;
  fp6_mul(t0, a.c0, a.c0);
  fp6_mul(t1, a.c1, a.c1);
  fp6_mul_nonres(t1, t1);
  fp6_sub(denom, t0, t1);
  fp6_inv(dinv, denom);
  fp6_mul(r.c0, a.c0, dinv);
  Fp6 n;
  fp6_neg(n, a.c1);
  fp6_mul(r.c1, n, dinv);
}

inline Fp2 frob_coeff(const uint64_t *c0m, const uint64_t *c1m) {
  Fp2 r;
  fp_set(r.c0, c0m);
  fp_set(r.c1, c1m);
  return r;
}

inline void fp12_frobenius(Fp12 &r, const Fp12 &a) {
  // one application of x -> x^q (port of fields.py::Fq12._frobenius_once)
  Fp2 f6c1 = frob_coeff(BLS_FROB6_C1_C0_M, BLS_FROB6_C1_C1_M);
  Fp2 f6c2 = frob_coeff(BLS_FROB6_C2_C0_M, BLS_FROB6_C2_C1_M);
  Fp2 f12 = frob_coeff(BLS_FROB12_C1_C0_M, BLS_FROB12_C1_C1_M);
  Fp6 c0, c1;
  fp2_conj(c0.c0, a.c0.c0);
  fp2_conj(c0.c1, a.c0.c1);
  fp2_mul(c0.c1, c0.c1, f6c1);
  fp2_conj(c0.c2, a.c0.c2);
  fp2_mul(c0.c2, c0.c2, f6c2);
  fp2_conj(c1.c0, a.c1.c0);
  fp2_mul(c1.c0, c1.c0, f12);
  fp2_conj(c1.c1, a.c1.c1);
  fp2_mul(c1.c1, c1.c1, f6c1);
  fp2_mul(c1.c1, c1.c1, f12);
  fp2_conj(c1.c2, a.c1.c2);
  fp2_mul(c1.c2, c1.c2, f6c2);
  fp2_mul(c1.c2, c1.c2, f12);
  r.c0 = c0;
  r.c1 = c1;
}

inline void fp12_cyclotomic_sqr(Fp12 &r, const Fp12 &f) {
  // Granger-Scott (port of fields.py::Fq12.cyclotomic_square)
  Fp2 z0 = f.c0.c0, z4 = f.c0.c1, z3 = f.c0.c2;
  Fp2 z2 = f.c1.c0, z1 = f.c1.c1, z5 = f.c1.c2;
  auto fp4_sq = [](Fp2 &o0, Fp2 &o1, const Fp2 &a0, const Fp2 &a1) {
    Fp2 t, s, u, sq;
    fp2_mul(t, a0, a1);
    fp2_add(s, a0, a1);
    fp2_mul_nonres(u, a1);
    fp2_add(u, a0, u);
    fp2_mul(sq, s, u);
    fp2_sub(sq, sq, t);
    Fp2 tn;
    fp2_mul_nonres(tn, t);
    fp2_sub(o0, sq, tn);
    fp2_add(o1, t, t);
  };
  Fp2 t0, t1, t2, t3, t4, t5;
  fp4_sq(t0, t1, z0, z1);
  fp4_sq(t2, t3, z2, z3);
  fp4_sq(t4, t5, z4, z5);
  auto three_minus_two = [](Fp2 &out, const Fp2 &t, const Fp2 &z) {
    // out = t + 2*(t - z)
    Fp2 d;
    fp2_sub(d, t, z);
    fp2_add(d, d, d);
    fp2_add(out, t, d);
  };
  auto three_plus_two = [](Fp2 &out, const Fp2 &t, const Fp2 &z) {
    // out = t + 2*(t + z)
    Fp2 d;
    fp2_add(d, t, z);
    fp2_add(d, d, d);
    fp2_add(out, t, d);
  };
  Fp2 nz0, nz1, nz2, nz3, nz4, nz5, nrt5;
  three_minus_two(nz0, t0, z0);
  three_plus_two(nz1, t1, z1);
  fp2_mul_nonres(nrt5, t5);
  three_plus_two(nz2, nrt5, z2);
  three_minus_two(nz3, t4, z3);
  three_minus_two(nz4, t2, z4);
  three_plus_two(nz5, t3, z5);
  r.c0.c0 = nz0;
  r.c0.c1 = nz4;
  r.c0.c2 = nz3;
  r.c1.c0 = nz2;
  r.c1.c1 = nz1;
  r.c1.c2 = nz5;
}

// ------------------------------------------------------------- G1 points
struct G1 {
  Fp x, y;  // affine, Montgomery form
  bool inf;
};

struct G1Jac {
  Fp x, y, z;  // z == 0 -> infinity
};

inline G1Jac g1_to_jac(const G1 &p) {
  if (p.inf) return {fp_one(), fp_one(), fp_zero()};
  return {p.x, p.y, fp_one()};
}

inline void g1_jac_dbl(G1Jac &r, const G1Jac &p) {
  if (fp_is_zero(p.z) || fp_is_zero(p.y)) {
    r = {fp_one(), fp_one(), fp_zero()};
    if (fp_is_zero(p.z)) r = p;
    return;
  }
  // dbl-2009-l (port of curve.py::_jac_double)
  Fp A, B, C, t, D, E, F, X3, Y3, Z3;
  fp_sqr(A, p.x);
  fp_sqr(B, p.y);
  fp_sqr(C, B);
  fp_add(t, p.x, B);
  fp_sqr(t, t);
  fp_sub(t, t, A);
  fp_sub(t, t, C);
  fp_add(D, t, t);
  fp_add(E, A, A);
  fp_add(E, E, A);
  fp_sqr(F, E);
  fp_sub(X3, F, D);
  fp_sub(X3, X3, D);
  Fp c8;
  fp_add(c8, C, C);
  fp_add(c8, c8, c8);
  fp_add(c8, c8, c8);
  fp_sub(t, D, X3);
  fp_mul(Y3, E, t);
  fp_sub(Y3, Y3, c8);
  fp_mul(Z3, p.y, p.z);
  fp_add(Z3, Z3, Z3);
  r = {X3, Y3, Z3};
}

inline void g1_jac_add(G1Jac &r, const G1Jac &p, const G1Jac &q) {
  if (fp_is_zero(p.z)) {
    r = q;
    return;
  }
  if (fp_is_zero(q.z)) {
    r = p;
    return;
  }
  // add-2007-bl (port of curve.py::_jac_add)
  Fp Z1Z1, Z2Z2, U1, U2, S1, S2, H, rr, I, J, V, X3, Y3, Z3, t;
  fp_sqr(Z1Z1, p.z);
  fp_sqr(Z2Z2, q.z);
  fp_mul(U1, p.x, Z2Z2);
  fp_mul(U2, q.x, Z1Z1);
  fp_mul(S1, p.y, q.z);
  fp_mul(S1, S1, Z2Z2);
  fp_mul(S2, q.y, p.z);
  fp_mul(S2, S2, Z1Z1);
  fp_sub(H, U2, U1);
  fp_sub(rr, S2, S1);
  if (fp_is_zero(H)) {
    if (fp_is_zero(rr)) {
      g1_jac_dbl(r, p);
      return;
    }
    r = {fp_one(), fp_one(), fp_zero()};
    return;
  }
  fp_add(I, H, H);
  fp_sqr(I, I);
  fp_mul(J, H, I);
  fp_add(rr, rr, rr);
  fp_mul(V, U1, I);
  fp_sqr(X3, rr);
  fp_sub(X3, X3, J);
  fp_sub(X3, X3, V);
  fp_sub(X3, X3, V);
  fp_sub(t, V, X3);
  fp_mul(Y3, rr, t);
  Fp S1J;
  fp_mul(S1J, S1, J);
  fp_sub(Y3, Y3, S1J);
  fp_sub(Y3, Y3, S1J);
  fp_add(Z3, p.z, q.z);
  fp_sqr(Z3, Z3);
  fp_sub(Z3, Z3, Z1Z1);
  fp_sub(Z3, Z3, Z2Z2);
  fp_mul(Z3, Z3, H);
  r = {X3, Y3, Z3};
}

inline void g1_jac_mul_jacbase(G1Jac &r, const G1Jac &b, const uint64_t *k,
                               int klimbs) {
  // Jacobian-base ladder: the membership test chains two ladders and
  // normalizing between them would cost a full Fermat inversion
  G1Jac acc = {fp_one(), fp_one(), fp_zero()};
  bool started = false;
  for (int i = klimbs - 1; i >= 0; i--) {
    for (int bit = 63; bit >= 0; bit--) {
      if (started) g1_jac_dbl(acc, acc);
      if ((k[i] >> bit) & 1) {
        g1_jac_add(acc, acc, b);
        started = true;
      }
    }
  }
  r = acc;
}

inline void g1_jac_mul(G1Jac &r, const G1 &base, const uint64_t *k, int klimbs) {
  g1_jac_mul_jacbase(r, g1_to_jac(base), k, klimbs);
}

inline G1 g1_from_jac(const G1Jac &p) {
  if (fp_is_zero(p.z)) return {fp_zero(), fp_zero(), true};
  Fp zi, zi2, zi3;
  fp_inv(zi, p.z);
  fp_sqr(zi2, zi);
  fp_mul(zi3, zi2, zi);
  G1 r;
  fp_mul(r.x, p.x, zi2);
  fp_mul(r.y, p.y, zi3);
  r.inf = false;
  return r;
}

// Full r-order ladder membership (the oracle the endomorphism test is
// parity-pinned against in tests; ~255 doubles + ~127 adds).
inline bool g1_in_subgroup_ladder(const G1 &p) {
  if (p.inf) return true;
  G1Jac t;
  g1_jac_mul(t, p, BLS_ORDER, 4);
  return fp_is_zero(t.z);
}

// GLV-endomorphism membership test: P in G1  <=>  phi(P) == -[x^2]P,
// where phi(x,y) = (beta*x, y) with beta the cube root of unity whose
// G1 eigenvalue is -x^2 mod r (x = the BLS parameter; beta derived
// from the framework's Python field oracle — see bls_constants.h).
// On G1 the identity holds because phi acts as an eigenvalue; for the
// cofactor torsion it fails (checked against the r-ladder oracle over
// raw curve / pure-cofactor / mixed / order-3 points — 3 divides the
// cofactor but x^2+1 = 2 mod 3, so order-3 components are rejected).
// Cost: two sparse |x|-ladders (~64 doubles + ~6 adds each) + 3 muls,
// vs the 255-bit order ladder — measured ~3x faster, and it runs per
// SIGNATURE in the distinct-digest storm path.
inline bool g1_in_subgroup(const G1 &p) {
  if (p.inf) return true;
  G1Jac q1;
  g1_jac_mul(q1, p, &BLS_X_ABS, 1);  // [|x|]P
  if (fp_is_zero(q1.z)) return false;  // ord(P) | |x|: phi(P) != O
  G1Jac q2;
  // chain in Jacobian coords — normalizing q1 would cost a Fermat
  // inversion, ~a third ladder's worth, per signature
  g1_jac_mul_jacbase(q2, q1, &BLS_X_ABS, 1);  // [x^2]P (x neg, squared)
  if (fp_is_zero(q2.z)) return false;
  // phi(P) == -q2, compared in Jacobian coords (no inversion):
  // beta*px * Z^2 == X2  and  py * Z^3 == -Y2
  Fp beta;
  fp_set(beta, BLS_BETA_TEST_M);
  Fp bx;
  fp_mul(bx, p.x, beta);
  Fp z2, z3, lhs;
  fp_sqr(z2, q2.z);
  fp_mul(z3, z2, q2.z);
  fp_mul(lhs, bx, z2);
  if (!fp_eq(lhs, q2.x)) return false;
  fp_mul(lhs, p.y, z3);
  Fp negy;
  fp_neg(negy, q2.y);
  return fp_eq(lhs, negy);
}

// decompress a 48-byte zcash-format G1 point; subgroup check optional
inline bool g1_from_bytes(G1 &out, const uint8_t *data, bool subgroup) {
  if (!(data[0] & 0x80)) return false;
  if (data[0] & 0x40) {  // infinity
    if (data[0] != 0xc0) return false;
    for (int i = 1; i < 48; i++)
      if (data[i]) return false;
    out = {fp_zero(), fp_zero(), true};
    return true;
  }
  bool sign = data[0] & 0x20;
  uint8_t buf[48];
  std::memcpy(buf, data, 48);
  buf[0] &= 0x1f;
  uint64_t raw[L];
  if (!fp_raw_from_be48(raw, buf)) return false;
  Fp x;
  fp_to_mont(x, raw);
  // y^2 = x^3 + 4
  Fp y2, t, b;
  fp_sqr(t, x);
  fp_mul(y2, t, x);
  fp_set(b, BLS_G1B_M);
  fp_add(y2, y2, b);
  Fp y;
  fp_pow(y, y2, BLS_QP1_4, L);
  Fp chk;
  fp_sqr(chk, y);
  if (!fp_eq(chk, y2)) return false;
  if (fp_canon_gt_half(y) != sign) fp_neg(y, y);
  out = {x, y, false};
  if (subgroup && !g1_in_subgroup(out)) return false;
  return true;
}

// ------------------------------------------------------------- G2 points
struct G2 {
  Fp2 x, y;
  bool inf;
};

struct G2Jac {
  Fp2 x, y, z;
};

inline void g2_jac_dbl(G2Jac &r, const G2Jac &p) {
  if (fp2_is_zero(p.z) || fp2_is_zero(p.y)) {
    if (fp2_is_zero(p.z)) {
      r = p;
      return;
    }
    r = {fp2_one(), fp2_one(), fp2_zero()};
    return;
  }
  Fp2 A, B, C, t, D, E, F, X3, Y3, Z3;
  fp2_sqr(A, p.x);
  fp2_sqr(B, p.y);
  fp2_sqr(C, B);
  fp2_add(t, p.x, B);
  fp2_sqr(t, t);
  fp2_sub(t, t, A);
  fp2_sub(t, t, C);
  fp2_add(D, t, t);
  fp2_add(E, A, A);
  fp2_add(E, E, A);
  fp2_sqr(F, E);
  fp2_sub(X3, F, D);
  fp2_sub(X3, X3, D);
  Fp2 c8;
  fp2_add(c8, C, C);
  fp2_add(c8, c8, c8);
  fp2_add(c8, c8, c8);
  fp2_sub(t, D, X3);
  fp2_mul(Y3, E, t);
  fp2_sub(Y3, Y3, c8);
  fp2_mul(Z3, p.y, p.z);
  fp2_add(Z3, Z3, Z3);
  r = {X3, Y3, Z3};
}

inline void g2_jac_add(G2Jac &r, const G2Jac &p, const G2Jac &q) {
  if (fp2_is_zero(p.z)) {
    r = q;
    return;
  }
  if (fp2_is_zero(q.z)) {
    r = p;
    return;
  }
  Fp2 Z1Z1, Z2Z2, U1, U2, S1, S2, H, rr, I, J, V, X3, Y3, Z3, t;
  fp2_sqr(Z1Z1, p.z);
  fp2_sqr(Z2Z2, q.z);
  fp2_mul(U1, p.x, Z2Z2);
  fp2_mul(U2, q.x, Z1Z1);
  fp2_mul(S1, p.y, q.z);
  fp2_mul(S1, S1, Z2Z2);
  fp2_mul(S2, q.y, p.z);
  fp2_mul(S2, S2, Z1Z1);
  fp2_sub(H, U2, U1);
  fp2_sub(rr, S2, S1);
  if (fp2_is_zero(H)) {
    if (fp2_is_zero(rr)) {
      g2_jac_dbl(r, p);
      return;
    }
    r = {fp2_one(), fp2_one(), fp2_zero()};
    return;
  }
  fp2_add(I, H, H);
  fp2_sqr(I, I);
  fp2_mul(J, H, I);
  fp2_add(rr, rr, rr);
  fp2_mul(V, U1, I);
  fp2_sqr(X3, rr);
  fp2_sub(X3, X3, J);
  fp2_sub(X3, X3, V);
  fp2_sub(X3, X3, V);
  fp2_sub(t, V, X3);
  fp2_mul(Y3, rr, t);
  Fp2 S1J;
  fp2_mul(S1J, S1, J);
  fp2_sub(Y3, Y3, S1J);
  fp2_sub(Y3, Y3, S1J);
  fp2_add(Z3, p.z, q.z);
  fp2_sqr(Z3, Z3);
  fp2_sub(Z3, Z3, Z1Z1);
  fp2_sub(Z3, Z3, Z2Z2);
  fp2_mul(Z3, Z3, H);
  r = {X3, Y3, Z3};
}

inline void g2_jac_mul(G2Jac &r, const G2 &base, const uint64_t *k, int klimbs) {
  G2Jac acc = {fp2_one(), fp2_one(), fp2_zero()};
  G2Jac b = {base.x, base.y, fp2_one()};
  bool started = false;
  for (int i = klimbs - 1; i >= 0; i--) {
    for (int bit = 63; bit >= 0; bit--) {
      if (started) g2_jac_dbl(acc, acc);
      if ((k[i] >> bit) & 1) {
        g2_jac_add(acc, acc, b);
        started = true;
      }
    }
  }
  r = acc;
}

inline bool g2_in_subgroup(const G2 &p) {
  if (p.inf) return true;
  G2Jac t;
  g2_jac_mul(t, p, BLS_ORDER, 4);
  return fp2_is_zero(t.z);
}

// "lexicographically large" for Fq2: c1 > half, or c1 == 0 and c0 > half
inline bool fp2_canon_gt_half(const Fp2 &a) {
  uint64_t raw1[L];
  fp_from_mont(raw1, a.c1);
  uint64_t zero1 = 0;
  for (int i = 0; i < L; i++) zero1 |= raw1[i];
  if (zero1 != 0) return fp_canon_gt_half(a.c1);
  return fp_canon_gt_half(a.c0);
}

inline bool g2_from_bytes(G2 &out, const uint8_t *data, bool subgroup) {
  if (!(data[0] & 0x80)) return false;
  if (data[0] & 0x40) {
    if (data[0] != 0xc0) return false;
    for (int i = 1; i < 96; i++)
      if (data[i]) return false;
    out = {fp2_zero(), fp2_zero(), true};
    return true;
  }
  bool sign = data[0] & 0x20;
  uint8_t buf[48];
  std::memcpy(buf, data, 48);
  buf[0] &= 0x1f;
  uint64_t raw1[L], raw0[L];
  if (!fp_raw_from_be48(raw1, buf)) return false;      // x.c1 (first 48)
  if (!fp_raw_from_be48(raw0, data + 48)) return false;  // x.c0
  Fp2 x;
  fp_to_mont(x.c1, raw1);
  fp_to_mont(x.c0, raw0);
  // y^2 = x^3 + 4(u+1)
  Fp2 y2, t, b2;
  fp2_sqr(t, x);
  fp2_mul(y2, t, x);
  Fp four;
  fp_set(four, BLS_G1B_M);  // Montgomery 4
  b2.c0 = four;
  b2.c1 = four;
  fp2_add(y2, y2, b2);
  Fp2 y;
  if (!fp2_sqrt(y, y2)) return false;
  if (fp2_canon_gt_half(y) != sign) fp2_neg(y, y);
  out = {x, y, false};
  if (subgroup && !g2_in_subgroup(out)) return false;
  return true;
}

// ------------------------------------------------------------ Miller loop
// Port of pairing.py::miller_loop with FULL fp12 line multiplication
// (the line value a + b*v + c*v*w embedded into Fp12 — simplicity over
// the 18-mul sparse product; C is fast enough).

inline Fp12 line_to_fp12(const Fp2 &a, const Fp2 &b, const Fp2 &c) {
  Fp12 r;
  r.c0.c0 = a;
  r.c0.c1 = b;
  r.c0.c2 = fp2_zero();
  r.c1.c0 = fp2_zero();
  r.c1.c1 = c;
  r.c1.c2 = fp2_zero();
  return r;
}

inline void miller_loop(Fp12 &f_out, const G1 &p, const G2 &q) {
  if (p.inf || q.inf) {
    f_out = fp12_one();
    return;
  }
  Fp2 xq = q.x, yq = q.y;
  G2Jac T = {xq, yq, fp2_one()};
  Fp12 f = fp12_one();
  // bits of |x| MSB-first, skipping the leading 1
  bool started = false;
  for (int bit = 63; bit >= 0; bit--) {
    bool one = (BLS_X_ABS >> bit) & 1;
    if (!started) {
      if (one) started = true;
      continue;
    }
    // tangent line at T, scaled by 2YZ^3:
    //   a = 3X^3 - 2Y^2, b = -3X^2 Z^2 xP, c = 2YZ^3 yP
    Fp2 X2, Y2, Z2, Z3, X3c, la, lb, lc, t;
    fp2_sqr(X2, T.x);
    fp2_sqr(Y2, T.y);
    fp2_sqr(Z2, T.z);
    fp2_mul(Z3, T.z, Z2);
    fp2_mul(X3c, T.x, X2);
    fp2_add(la, X3c, X3c);
    fp2_add(la, la, X3c);
    fp2_sub(la, la, Y2);
    fp2_sub(la, la, Y2);
    Fp2 x2_3;
    fp2_add(x2_3, X2, X2);
    fp2_add(x2_3, x2_3, X2);
    fp2_mul(lb, x2_3, Z2);
    fp2_mul_fp(lb, lb, p.x);
    fp2_neg(lb, lb);
    fp2_add(t, T.y, T.y);
    fp2_mul(lc, t, Z3);
    fp2_mul_fp(lc, lc, p.y);
    fp12_sqr(f, f);
    Fp12 lf = line_to_fp12(la, lb, lc);
    fp12_mul(f, f, lf);
    g2_jac_dbl(T, T);
    if (one) {
      // chord through T and Q, scaled by Z^3 * D
      Fp2 n, d;
      fp2_sqr(Z2, T.z);
      fp2_mul(Z3, T.z, Z2);
      fp2_mul(n, yq, Z3);
      fp2_sub(n, n, T.y);
      fp2_mul(d, xq, Z2);
      fp2_sub(d, d, T.x);
      Fp2 yd;
      fp2_mul(la, n, T.x);
      fp2_mul(yd, T.y, d);
      fp2_sub(la, la, yd);
      fp2_mul(lb, n, Z2);
      fp2_mul_fp(lb, lb, p.x);
      fp2_neg(lb, lb);
      fp2_mul(lc, Z3, d);
      fp2_mul_fp(lc, lc, p.y);
      Fp12 lf2 = line_to_fp12(la, lb, lc);
      fp12_mul(f, f, lf2);
      G2Jac qj = {xq, yq, fp2_one()};
      g2_jac_add(T, T, qj);
    }
  }
  // X < 0: conjugate
  fp12_conj(f, f);
  f_out = f;
}

// ------------------------------------------------- prepared Miller loop
// Committee public keys are FIXED per epoch, so the G2-side work of
// every Miller loop — tangent/chord line coefficients and the T-point
// ladder — can be computed once per key and cached (the standard
// "prepared pairing" decomposition).  Evaluation then only scales each
// step's (b, c) coefficients by the G1 point's affine coordinates and
// folds the sparse line into the accumulator.  Measured on this rig it
// takes the per-entry Miller cost from ~1.5 ms to ~0.8 ms, which is
// what makes the distinct-digest TC storm target reachable
// (VERDICT r5 item 8).

struct LineCoeff {
  Fp2 a, b, c;  // unscaled: evaluation multiplies b by xP and c by yP
};

struct G2Prepared {
  bool inf = false;
  std::vector<LineCoeff> coeffs;
};

inline void g2_prepare(G2Prepared &out, const G2 &q) {
  out.inf = q.inf;
  out.coeffs.clear();
  if (q.inf) return;
  Fp2 xq = q.x, yq = q.y;
  G2Jac T = {xq, yq, fp2_one()};
  bool started = false;
  for (int bit = 63; bit >= 0; bit--) {
    bool one = (BLS_X_ABS >> bit) & 1;
    if (!started) {
      if (one) started = true;
      continue;
    }
    // tangent line at T (same algebra as miller_loop, px/py unscaled)
    Fp2 X2, Y2, Z2, Z3, X3c, t;
    LineCoeff L;
    fp2_sqr(X2, T.x);
    fp2_sqr(Y2, T.y);
    fp2_sqr(Z2, T.z);
    fp2_mul(Z3, T.z, Z2);
    fp2_mul(X3c, T.x, X2);
    fp2_add(L.a, X3c, X3c);
    fp2_add(L.a, L.a, X3c);
    fp2_sub(L.a, L.a, Y2);
    fp2_sub(L.a, L.a, Y2);
    Fp2 x2_3;
    fp2_add(x2_3, X2, X2);
    fp2_add(x2_3, x2_3, X2);
    fp2_mul(L.b, x2_3, Z2);
    fp2_neg(L.b, L.b);
    fp2_add(t, T.y, T.y);
    fp2_mul(L.c, t, Z3);
    out.coeffs.push_back(L);
    g2_jac_dbl(T, T);
    if (one) {
      // chord through T and Q
      Fp2 n, d, yd;
      LineCoeff M;
      fp2_sqr(Z2, T.z);
      fp2_mul(Z3, T.z, Z2);
      fp2_mul(n, yq, Z3);
      fp2_sub(n, n, T.y);
      fp2_mul(d, xq, Z2);
      fp2_sub(d, d, T.x);
      fp2_mul(M.a, n, T.x);
      fp2_mul(yd, T.y, d);
      fp2_sub(M.a, M.a, yd);
      fp2_mul(M.b, n, Z2);
      fp2_neg(M.b, M.b);
      fp2_mul(M.c, Z3, d);
      out.coeffs.push_back(M);
      G2Jac qj = {xq, yq, fp2_one()};
      g2_jac_add(T, T, qj);
    }
  }
}

// f *= line, exploiting the line's sparsity: c0 = (a, b, 0), c1 =
// (0, c, 0).  13 fp2 multiplications instead of fp12_mul's 18.
inline void fp12_mul_by_line(Fp12 &f, const Fp2 &a, const Fp2 &b,
                             const Fp2 &c) {
  const Fp6 &f0 = f.c0;
  const Fp6 &f1 = f.c1;
  // t0 = f0 * (a, b, 0)
  Fp6 t0;
  {
    Fp2 xa, yb, zb, za, k, s, u;
    fp2_mul(xa, f0.c0, a);
    fp2_mul(yb, f0.c1, b);
    fp2_mul(zb, f0.c2, b);
    fp2_mul(za, f0.c2, a);
    fp2_add(s, f0.c0, f0.c1);
    fp2_add(u, a, b);
    fp2_mul(k, s, u);  // (x+y)(a+b)
    fp2_mul_nonres(t0.c0, zb);
    fp2_add(t0.c0, t0.c0, xa);
    fp2_sub(t0.c1, k, xa);
    fp2_sub(t0.c1, t0.c1, yb);
    fp2_add(t0.c2, za, yb);
  }
  // t1 = f1 * (0, c, 0)
  Fp6 t1;
  {
    Fp2 yc, zc, xc;
    fp2_mul(xc, f1.c0, c);
    fp2_mul(yc, f1.c1, c);
    fp2_mul(zc, f1.c2, c);
    fp2_mul_nonres(t1.c0, zc);
    t1.c1 = xc;
    t1.c2 = yc;
  }
  // c1 = (f0 + f1) * (a, b + c, 0) - t0 - t1
  Fp6 c1;
  {
    Fp6 s6;
    fp6_add(s6, f0, f1);
    Fp2 bc;
    fp2_add(bc, b, c);
    Fp2 xa, ybc, zbc, za, k, s, u;
    fp2_mul(xa, s6.c0, a);
    fp2_mul(ybc, s6.c1, bc);
    fp2_mul(zbc, s6.c2, bc);
    fp2_mul(za, s6.c2, a);
    fp2_add(s, s6.c0, s6.c1);
    fp2_add(u, a, bc);
    fp2_mul(k, s, u);
    fp6_sub(c1, fp6_zero(), t0);  // start at -t0
    Fp6 prod;
    fp2_mul_nonres(prod.c0, zbc);
    fp2_add(prod.c0, prod.c0, xa);
    fp2_sub(prod.c1, k, xa);
    fp2_sub(prod.c1, prod.c1, ybc);
    fp2_add(prod.c2, za, ybc);
    fp6_add(c1, c1, prod);
    fp6_sub(c1, c1, t1);
  }
  // c0 = t0 + nonres(t1)
  Fp6 t1n;
  fp6_mul_nonres(t1n, t1);
  fp6_add(f.c0, t0, t1n);
  f.c1 = c1;
}

inline void miller_loop_prepared(Fp12 &f_out, const G1 &p,
                                 const G2Prepared &q) {
  if (p.inf || q.inf) {
    f_out = fp12_one();
    return;
  }
  Fp12 f = fp12_one();
  size_t idx = 0;
  bool started = false;
  for (int bit = 63; bit >= 0; bit--) {
    bool one = (BLS_X_ABS >> bit) & 1;
    if (!started) {
      if (one) started = true;
      continue;
    }
    fp12_sqr(f, f);
    {
      const LineCoeff &L = q.coeffs[idx++];
      Fp2 lb, lc;
      fp2_mul_fp(lb, L.b, p.x);
      fp2_mul_fp(lc, L.c, p.y);
      fp12_mul_by_line(f, L.a, lb, lc);
    }
    if (one) {
      const LineCoeff &M = q.coeffs[idx++];
      Fp2 lb, lc;
      fp2_mul_fp(lb, M.b, p.x);
      fp2_mul_fp(lc, M.c, p.y);
      fp12_mul_by_line(f, M.a, lb, lc);
    }
  }
  fp12_conj(f, f);
  f_out = f;
}

// per-epoch cache: compressed pk bytes -> prepared line coefficients.
// Entries are shared_ptr so eviction can clear the map while another
// verifier thread (AsyncVerifyService executor) is still mid-loop on a
// previously returned entry — the in-flight reference keeps it alive
// (returning raw pointers here would be a use-after-free on eviction).
inline std::shared_ptr<const G2Prepared> g2_prepared_cached(
    const uint8_t *pk96, const G2 &q) {
  static std::unordered_map<std::string, std::shared_ptr<const G2Prepared>>
      cache;
  static std::mutex mu;
  std::string key(reinterpret_cast<const char *>(pk96), 96);
  {
    std::lock_guard<std::mutex> g(mu);
    auto it = cache.find(key);
    if (it != cache.end()) return it->second;
  }
  auto prep = std::make_shared<G2Prepared>();
  g2_prepare(*prep, q);
  {
    std::lock_guard<std::mutex> g(mu);
    auto it = cache.find(key);
    if (it != cache.end()) return it->second;
    if (cache.size() > 8192) cache.clear();  // epoch churn bound
    cache.emplace(std::move(key), prep);
  }
  return prep;
}

inline G2 g2_generator();  // defined below

inline const G2Prepared &g2_generator_prepared() {
  static G2Prepared prep;
  static std::once_flag once;
  std::call_once(once, [] { g2_prepare(prep, g2_generator()); });
  return prep;
}

// f^|x| on cyclotomic elements (Granger-Scott squarings)
inline void pow_abs_x(Fp12 &r, const Fp12 &f) {
  Fp12 acc = f;
  bool started = false;
  for (int bit = 63; bit >= 0; bit--) {
    bool one = (BLS_X_ABS >> bit) & 1;
    if (!started) {
      if (one) started = true;
      continue;
    }
    fp12_cyclotomic_sqr(acc, acc);
    if (one) fp12_mul(acc, acc, f);
  }
  r = acc;
}

inline void pow_x(Fp12 &r, const Fp12 &f) {
  Fp12 t;
  pow_abs_x(t, f);
  fp12_conj(r, t);  // X < 0: conjugate = inverse in cyclotomic subgroup
}

inline void final_exponentiation(Fp12 &r, const Fp12 &f_in) {
  // easy part: f^((q^6-1)(q^2+1))
  Fp12 fc, fi, t, f;
  fp12_conj(fc, f_in);
  fp12_inv(fi, f_in);
  fp12_mul(t, fc, fi);  // f^(q^6 - 1)
  Fp12 tf;
  fp12_frobenius(tf, t);
  fp12_frobenius(tf, tf);
  fp12_mul(f, tf, t);  // ^(q^2 + 1)
  // hard part: ^((x-1)^2 (x+q) (x^2+q^2-1)) * f^3
  Fp12 t1, t2, t3, tmp;
  pow_x(t1, f);
  fp12_conj(tmp, f);
  fp12_mul(t1, t1, tmp);  // f^(x-1)
  pow_x(tmp, t1);
  Fp12 t1c;
  fp12_conj(t1c, t1);
  fp12_mul(t1, tmp, t1c);  // ^(x-1)^2
  pow_x(t2, t1);
  fp12_frobenius(tmp, t1);
  fp12_mul(t2, t2, tmp);  // ^(x+q)
  pow_x(t3, t2);
  pow_x(t3, t3);  // ^x^2
  fp12_frobenius(tmp, t2);
  fp12_frobenius(tmp, tmp);
  fp12_mul(t3, t3, tmp);
  Fp12 t2c;
  fp12_conj(t2c, t2);
  fp12_mul(t3, t3, t2c);  // ^(x^2+q^2-1)
  Fp12 f2;
  fp12_sqr(f2, f);
  fp12_mul(f2, f2, f);  // f^3
  fp12_mul(r, t3, f2);
}

inline bool pairings_equal(const G1 &p1, const G2 &q1, const G1 &p2,
                           const G2 &q2) {
  // e(P1,Q1) == e(P2,Q2)  via  e(P1,Q1) * e(-P2,Q2) == 1
  G1 np2 = p2;
  if (!np2.inf) fp_neg(np2.y, np2.y);
  Fp12 f1, f2, f, out;
  miller_loop(f1, p1, q1);
  miller_loop(f2, np2, q2);
  fp12_mul(f, f1, f2);
  final_exponentiation(out, f);
  return fp12_eq(out, fp12_one());
}

// ---------------------------------------------------------------- SHA-256
struct Sha256 {
  uint32_t h[8];
  uint8_t buf[64];
  uint64_t len;
  size_t fill;
};

inline uint32_t rotr(uint32_t x, int n) { return (x >> n) | (x << (32 - n)); }

static const uint32_t K256[64] = {
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2};

inline void sha256_init(Sha256 &s) {
  static const uint32_t H0[8] = {0x6a09e667, 0xbb67ae85, 0x3c6ef372,
                                 0xa54ff53a, 0x510e527f, 0x9b05688c,
                                 0x1f83d9ab, 0x5be0cd19};
  std::memcpy(s.h, H0, sizeof H0);
  s.len = 0;
  s.fill = 0;
}

inline void sha256_block(Sha256 &s, const uint8_t *p) {
  uint32_t w[64];
  for (int i = 0; i < 16; i++)
    w[i] = (uint32_t(p[4 * i]) << 24) | (uint32_t(p[4 * i + 1]) << 16) |
           (uint32_t(p[4 * i + 2]) << 8) | uint32_t(p[4 * i + 3]);
  for (int i = 16; i < 64; i++) {
    uint32_t s0 = rotr(w[i - 15], 7) ^ rotr(w[i - 15], 18) ^ (w[i - 15] >> 3);
    uint32_t s1 = rotr(w[i - 2], 17) ^ rotr(w[i - 2], 19) ^ (w[i - 2] >> 10);
    w[i] = w[i - 16] + s0 + w[i - 7] + s1;
  }
  uint32_t a = s.h[0], b = s.h[1], c = s.h[2], d = s.h[3], e = s.h[4],
           f = s.h[5], g = s.h[6], h = s.h[7];
  for (int i = 0; i < 64; i++) {
    uint32_t S1 = rotr(e, 6) ^ rotr(e, 11) ^ rotr(e, 25);
    uint32_t ch = (e & f) ^ (~e & g);
    uint32_t temp1 = h + S1 + ch + K256[i] + w[i];
    uint32_t S0 = rotr(a, 2) ^ rotr(a, 13) ^ rotr(a, 22);
    uint32_t maj = (a & b) ^ (a & c) ^ (b & c);
    uint32_t temp2 = S0 + maj;
    h = g;
    g = f;
    f = e;
    e = d + temp1;
    d = c;
    c = b;
    b = a;
    a = temp1 + temp2;
  }
  s.h[0] += a;
  s.h[1] += b;
  s.h[2] += c;
  s.h[3] += d;
  s.h[4] += e;
  s.h[5] += f;
  s.h[6] += g;
  s.h[7] += h;
}

inline void sha256_update(Sha256 &s, const uint8_t *data, size_t n) {
  s.len += n;
  while (n) {
    size_t take = 64 - s.fill;
    if (take > n) take = n;
    std::memcpy(s.buf + s.fill, data, take);
    s.fill += take;
    data += take;
    n -= take;
    if (s.fill == 64) {
      sha256_block(s, s.buf);
      s.fill = 0;
    }
  }
}

inline void sha256_final(Sha256 &s, uint8_t out[32]) {
  uint64_t bitlen = s.len * 8;
  uint8_t pad = 0x80;
  sha256_update(s, &pad, 1);
  uint8_t z = 0;
  while (s.fill != 56) sha256_update(s, &z, 1);
  uint8_t lenb[8];
  for (int i = 0; i < 8; i++) lenb[i] = (uint8_t)(bitlen >> (8 * (7 - i)));
  sha256_update(s, lenb, 8);
  for (int i = 0; i < 8; i++) {
    out[4 * i] = (uint8_t)(s.h[i] >> 24);
    out[4 * i + 1] = (uint8_t)(s.h[i] >> 16);
    out[4 * i + 2] = (uint8_t)(s.h[i] >> 8);
    out[4 * i + 3] = (uint8_t)s.h[i];
  }
}

// ------------------------------------------------------------- hash_to_g1
// Port of curve.py::hash_to_g1 (framework-internal deterministic map —
// NOT RFC 9380; both sides must match bit for bit).

inline void be48_mod_q(uint64_t out[L], const uint8_t be[48]) {
  for (int i = 0; i < L; i++) {
    uint64_t w = 0;
    for (int j = 0; j < 8; j++) w = (w << 8) | be[(L - 1 - i) * 8 + j];
    out[i] = w;
  }
  // value < 2^384, q ~ 2^381.6 -> at most ~6 subtractions
  while (fp_geq(out, BLS_Q)) fp_sub_raw(out, out, BLS_Q);
}

inline void hash_to_g1_base(G1 &out, const uint8_t *msg, size_t msg_len,
                            const uint8_t *dst, size_t dst_len) {
  // the pre-cofactor map: device offload clears the cofactor inside
  // its combined (weight x h_eff) ladder
  for (uint32_t counter = 0;; counter++) {
    uint8_t ctr[4] = {(uint8_t)(counter >> 24), (uint8_t)(counter >> 16),
                      (uint8_t)(counter >> 8), (uint8_t)counter};
    uint8_t h[32], h2[32];
    Sha256 s;
    sha256_init(s);
    sha256_update(s, dst, dst_len);
    sha256_update(s, ctr, 4);
    sha256_update(s, msg, msg_len);
    sha256_final(s, h);
    Sha256 s2;
    sha256_init(s2);
    const uint8_t tag[2] = {'x', '2'};
    sha256_update(s2, tag, 2);
    sha256_update(s2, h, 32);
    sha256_final(s2, h2);
    uint8_t xbe[48];
    std::memcpy(xbe, h, 32);
    std::memcpy(xbe + 32, h2, 16);
    uint64_t raw[L];
    be48_mod_q(raw, xbe);
    Fp x;
    fp_to_mont(x, raw);
    Fp y2, t, b;
    fp_sqr(t, x);
    fp_mul(y2, t, x);
    fp_set(b, BLS_G1B_M);
    fp_add(y2, y2, b);
    Fp y, chk;
    fp_pow(y, y2, BLS_QP1_4, L);
    fp_sqr(chk, y);
    if (!fp_eq(chk, y2)) continue;
    // pick the "even" root: NOT lexicographically large
    if (fp_canon_gt_half(y)) fp_neg(y, y);
    out = {x, y, false};
    return;
  }
}

inline void hash_to_g1(G1 &out, const uint8_t *msg, size_t msg_len,
                       const uint8_t *dst, size_t dst_len) {
  G1 base;
  hash_to_g1_base(base, msg, msg_len, dst, dst_len);
  G1Jac cleared;
  g1_jac_mul(cleared, base, BLS_H1, 2);
  out = g1_from_jac(cleared);
}

// ------------------------------------------------------------- signing
// sk*[h1]B for the hash base B, the point hash_to_g1(msg).mul(sk) gives,
// in about half the work of two ladders:
// - h1 = (1 - x)*c with c = (1 - x)/3, and [1 - x] maps every point of
//   the curve into G1 (RFC 9380 8.8.1's h_eff for G1), so
//   sk*[h1]B = [sk*c mod r]([1 - x]B): a sparse 64-bit ladder clears
//   the cofactor and the rest of h1 is folded into the scalar;
// - on G1, phi(X:Y:Z) = (beta*X : Y : Z) is -[x^2] (g1_in_subgroup), so
//   with k = k2*x^2 + k1 (k1, k2 < x^2 < 2^128, as r < x^4),
//   [k]P = [k1]P + [k2](-phi(P)): one interleaved width-5 wNAF pass of
//   ~128 doublings and ~43 additions, where the ladder took 255 and ~128;
// - Jacobian from the cofactor ladder to the end: one inversion.
// Like the ladder it replaced, this branches on the key's bits: neither
// is constant time.

static const uint64_t BLS_H_EFF = BLS_X_ABS + 1;  // 1 - x, as x = -|x|
static const uint64_t BLS_H1_C = BLS_H_EFF / 3;    // h1 = (1 - x)*c
static_assert(BLS_H_EFF % 3 == 0, "3 divides 1 - x");

// a + b mod r for a, b < r (r < 2^255, so the sum fits in four limbs)
inline void sc_add_mod_r(uint64_t out[4], const uint64_t a[4],
                         const uint64_t b[4]) {
  unsigned __int128 carry = 0;
  for (int i = 0; i < 4; i++) {
    unsigned __int128 s = (unsigned __int128)a[i] + b[i] + (uint64_t)carry;
    out[i] = (uint64_t)s;
    carry = s >> 64;
  }
  for (int i = 3; i >= 0; i--) {
    if (out[i] > BLS_ORDER[i]) break;
    if (out[i] < BLS_ORDER[i]) return;
  }
  unsigned __int128 borrow = 0;
  for (int i = 0; i < 4; i++) {
    unsigned __int128 d =
        (unsigned __int128)out[i] - BLS_ORDER[i] - (uint64_t)borrow;
    out[i] = (uint64_t)d;
    borrow = (d >> 64) ? 1 : 0;
  }
}

// a*m mod r for a < r, by double-and-add over the bits of m
inline void sc_mul_small_mod_r(uint64_t out[4], const uint64_t a[4],
                               uint64_t m) {
  uint64_t acc[4] = {0, 0, 0, 0};
  for (int bit = 63; bit >= 0; bit--) {
    sc_add_mod_r(acc, acc, acc);
    if ((m >> bit) & 1) sc_add_mod_r(acc, acc, a);
  }
  std::memcpy(out, acc, sizeof acc);
}

// k = k2*x^2 + k1 with k1 < x^2, for k < r: two divisions by |x|
inline void glv_split(unsigned __int128 &k1, unsigned __int128 &k2,
                      const uint64_t k[4]) {
  uint64_t q1[4], q2[4];
  unsigned __int128 r1 = 0, r2 = 0;
  for (int i = 3; i >= 0; i--) {
    unsigned __int128 cur = (r1 << 64) | k[i];
    q1[i] = (uint64_t)(cur / BLS_X_ABS);
    r1 = cur % BLS_X_ABS;
  }
  for (int i = 3; i >= 0; i--) {
    unsigned __int128 cur = (r2 << 64) | q1[i];
    q2[i] = (uint64_t)(cur / BLS_X_ABS);
    r2 = cur % BLS_X_ABS;
  }
  k2 = ((unsigned __int128)q2[1] << 64) | q2[0];  // q2[2..3] = 0 as k < r
  k1 = r2 * BLS_X_ABS + r1;
}

// width-5 NAF of k < x^2, least significant digit first: digits 0 or odd
// in [-15, 15]; returns the digit count (at most 129)
inline int wnaf5(int8_t d[130], unsigned __int128 k) {
  int n = 0;
  while (k) {
    int di = 0;
    if (k & 1) {
      di = (int)(k & 31);
      if (di >= 16) di -= 32;
      if (di > 0)
        k -= (unsigned)di;
      else
        k += (unsigned)-di;  // k + 15 < 2^128: no wrap
    }
    d[n++] = (int8_t)di;
    k >>= 1;
  }
  return n;
}

// acc += d*T for a wNAF digit d and the table T of odd multiples
inline void g1_jac_add_digit(G1Jac &acc, const G1Jac t[8], int d) {
  if (d > 0) {
    g1_jac_add(acc, acc, t[d >> 1]);
  } else if (d < 0) {
    G1Jac neg = t[(-d) >> 1];
    fp_neg(neg.y, neg.y);
    g1_jac_add(acc, acc, neg);
  }
}

// [k]P for P in G1 and k < r, by the GLV split
inline void g1_glv_mul(G1Jac &r, const G1Jac &p, const uint64_t k[4]) {
  unsigned __int128 k1, k2;
  glv_split(k1, k2, k);
  // P, 3P, ..., 15P, and -phi of each: (beta*X, -Y, Z)
  G1Jac t1[8], t2[8], p2;
  t1[0] = p;
  g1_jac_dbl(p2, p);
  for (int i = 1; i < 8; i++) g1_jac_add(t1[i], t1[i - 1], p2);
  Fp beta;
  fp_set(beta, BLS_BETA_TEST_M);
  for (int i = 0; i < 8; i++) {
    fp_mul(t2[i].x, t1[i].x, beta);
    fp_neg(t2[i].y, t1[i].y);
    t2[i].z = t1[i].z;
  }
  int8_t d1[130], d2[130];
  int n1 = wnaf5(d1, k1), n2 = wnaf5(d2, k2);
  G1Jac acc = {fp_one(), fp_one(), fp_zero()};
  for (int i = (n1 > n2 ? n1 : n2) - 1; i >= 0; i--) {
    g1_jac_dbl(acc, acc);
    if (i < n1) g1_jac_add_digit(acc, t1, d1[i]);
    if (i < n2) g1_jac_add_digit(acc, t2, d2[i]);
  }
  r = acc;
}

// Decompressed-pk cache: committee keys repeat across every verify
// call, and G2 decompression costs an Fq2 sqrt (~0.3 ms) plus an
// optional subgroup ladder.  Keyed by the raw 96 compressed bytes;
// entries are stored SUBGROUP-CHECKED so a hit satisfies the strictest
// caller.  Bounded; cleared when full (worst case = re-decompression).
struct PkCacheEntry {
  G2 point;
  bool on_curve;
  bool in_subgroup;
};

inline bool g2_from_bytes_cached(G2 &out, const uint8_t *data,
                                 bool subgroup) {
  static std::unordered_map<std::string, PkCacheEntry> cache;
  static std::mutex mu;
  std::string key(reinterpret_cast<const char *>(data), 96);
  {
    std::lock_guard<std::mutex> g(mu);
    auto it = cache.find(key);
    if (it != cache.end()) {
      const PkCacheEntry &e = it->second;
      if (!e.on_curve) return false;
      if (subgroup && !e.in_subgroup) return false;
      out = e.point;
      return true;
    }
  }
  G2 p;
  bool on_curve = g2_from_bytes(p, data, /*subgroup=*/false);
  bool in_sub = on_curve && g2_in_subgroup(p);
  {
    std::lock_guard<std::mutex> g(mu);
    if (cache.size() > 8192) cache.clear();
    cache.emplace(std::move(key), PkCacheEntry{p, on_curve, in_sub});
  }
  if (!on_curve) return false;
  if (subgroup && !in_sub) return false;
  out = p;
  return true;
}

inline G2 g2_generator() {
  G2 g;
  fp_set(g.x.c0, BLS_G2X0_M);
  fp_set(g.x.c1, BLS_G2X1_M);
  fp_set(g.y.c0, BLS_G2Y0_M);
  fp_set(g.y.c1, BLS_G2Y1_M);
  g.inf = false;
  return g;
}

}  // namespace

namespace {

// canonical compressed encodings (zcash format, matching curve.py)
inline void g1_to_bytes(uint8_t out[48], const G1 &p) {
  if (p.inf) {
    std::memset(out, 0, 48);
    out[0] = 0xc0;
    return;
  }
  uint64_t raw[L];
  fp_from_mont(raw, p.x);
  for (int i = 0; i < L; i++)
    for (int j = 0; j < 8; j++)
      out[(L - 1 - i) * 8 + j] = (uint8_t)(raw[i] >> (8 * (7 - j)));
  out[0] |= 0x80;
  if (fp_canon_gt_half(p.y)) out[0] |= 0x20;
}

// uncompressed affine (x||y, 48 B big-endian each) — the exchange
// format between this library and the TPU G1 ladder (tpu/bls.py):
// decompression/hashing happens here, scalar ladders on device, and
// the resulting points come back for the pairing product.
inline void fp_to_be48(uint8_t out[48], const Fp &a) {
  uint64_t raw[L];
  fp_from_mont(raw, a);
  for (int i = 0; i < L; i++)
    for (int j = 0; j < 8; j++)
      out[(L - 1 - i) * 8 + j] = (uint8_t)(raw[i] >> (8 * (7 - j)));
}

inline bool fp_from_be48(Fp &out, const uint8_t in[48]) {
  uint64_t raw[L];
  for (int i = 0; i < L; i++) {
    uint64_t w = 0;
    for (int j = 0; j < 8; j++) w = (w << 8) | in[(L - 1 - i) * 8 + j];
    raw[i] = w;
  }
  if (fp_geq(raw, BLS_Q)) return false;
  fp_to_mont(out, raw);
  return true;
}

inline void g1_to_uncompressed(uint8_t out[96], const G1 &p) {
  if (p.inf) {
    std::memset(out, 0, 96);
    return;  // (0, 0) is not on the curve (b=4): unambiguous infinity
  }
  fp_to_be48(out, p.x);
  fp_to_be48(out + 48, p.y);
}

inline bool g1_from_uncompressed(G1 &out, const uint8_t in[96]) {
  bool all_zero = true;
  for (int i = 0; i < 96; i++)
    if (in[i]) {
      all_zero = false;
      break;
    }
  if (all_zero) {
    out = {fp_zero(), fp_zero(), true};
    return true;
  }
  if (!fp_from_be48(out.x, in) || !fp_from_be48(out.y, in + 48))
    return false;
  out.inf = false;
  // on-curve check: y^2 == x^3 + 4
  Fp y2, x3, b;
  fp_sqr(y2, out.y);
  fp_sqr(x3, out.x);
  fp_mul(x3, x3, out.x);
  fp_set(b, BLS_G1B_M);
  fp_add(x3, x3, b);
  return fp_eq(y2, x3);
}

}  // namespace

// ----------------------------------------------------------------- C API
extern "C" {

// Sum n compressed G1 signatures (48 B each, contiguous) into out48.
// Decompression checks on-curve only — callers subgroup-check the
// AGGREGATE (hs_bls_verify_one_ex does).  Returns 1 ok / 0 malformed.
int hs_bls_aggregate_sigs(const uint8_t *sigs, size_t n, uint8_t *out48) {
  G1Jac acc = {fp_one(), fp_one(), fp_zero()};
  for (size_t i = 0; i < n; i++) {
    G1 p;
    if (!g1_from_bytes(p, sigs + 48 * i, /*subgroup=*/false)) return 0;
    if (p.inf) continue;
    G1Jac pj = g1_to_jac(p);
    g1_jac_add(acc, acc, pj);
  }
  G1 aff = g1_from_jac(acc);
  g1_to_bytes(out48, aff);
  return 1;
}

// NOTE: a native G2 public-key aggregate was tried and REMOVED — it
// lost to summing the verifier's cached decoded Python points, because
// the native path must re-run the expensive Fq2 sqrt per key that the
// cache pays once per epoch (docs/ROUND2.md records the experiment).

// Batched distinct-message verification (the TC / view-change-storm
// shape) by the random-weight small-exponents technique:
//   e(Σ rᵢ·sigᵢ, G2) == Π e(rᵢ·H(mᵢ), pkᵢ)
// — n+1 Miller loops sharing ONE final exponentiation instead of n
// full pairing equalities.  msgs32: n contiguous 32-byte digests;
// weights16: n contiguous 16-byte little-endian nonzero random weights
// (HOST-generated — they are what makes cross-entry cancellation
// infeasible); check_pk_subgroup = 0 only for keys the caller already
// validated (committee cache).  Every signature is individually
// subgroup-checked (see the in-loop comment).  Returns 1 = every entry valid; 0 = at
// least one invalid/malformed (caller re-checks per item to pinpoint).
int hs_bls_verify_batch(const uint8_t *msgs32, const uint8_t *pks96,
                        const uint8_t *sigs48, size_t n,
                        const uint8_t *weights16, int check_pk_subgroup) {
  // check_pk_subgroup == 0 marks per-batch AGGREGATE keys (the grouped
  // TC path): they never repeat, so caching their ~20 KB prepared line
  // coefficients would only pollute (and eventually flush) the
  // committee-key cache — prepare them on the stack instead
  const bool cache_pks = check_pk_subgroup != 0;
  if (n == 0) return 0;
  static const uint8_t DST[] = "HOTSTUFF_TPU_BLS_G1";
  G1Jac sig_acc = {fp_one(), fp_one(), fp_zero()};
  Fp12 f = fp12_one();
  for (size_t i = 0; i < n; i++) {
    G2 pk;
    if (cache_pks) {
      if (!g2_from_bytes_cached(pk, pks96 + 96 * i, /*subgroup=*/true))
        return 0;
    } else {
      // one-shot aggregate keys: plain decode, no subgroup ladder (the
      // flag's contract), and no decode-cache insertion — the cached
      // path would run the ladder on every miss anyway and grow the
      // cache toward the clear() that evicts the real committee keys
      if (!g2_from_bytes(pk, pks96 + 96 * i, /*subgroup=*/false)) return 0;
    }
    if (pk.inf) return 0;
    G1 sig;
    // per-signature subgroup check: the G1 cofactor has SMALL factors
    // (3, 11, ...), so a small-order component T on one signature
    // survives the weighted-aggregate ladder whenever the random
    // weight is divisible by ord(T) (probability 1/3 for order 3) —
    // an aggregate-only check is NOT sound here, unlike the
    // shared-message path where failures fall back to per-item checks
    if (!g1_from_bytes(sig, sigs48 + 48 * i, /*subgroup=*/true)) return 0;
    if (sig.inf) return 0;
    uint64_t w[2];
    w[0] = w[1] = 0;
    for (int b = 0; b < 8; b++) {
      w[0] |= (uint64_t)weights16[16 * i + b] << (8 * b);
      w[1] |= (uint64_t)weights16[16 * i + 8 + b] << (8 * b);
    }
    if ((w[0] | w[1]) == 0) return 0;  // zero weight defeats the check
    G1Jac wsig;
    g1_jac_mul(wsig, sig, w, 2);
    g1_jac_add(sig_acc, sig_acc, wsig);
    G1 hm;
    hash_to_g1(hm, msgs32 + 32 * i, 32, DST, sizeof(DST) - 1);
    G1Jac whm_j;
    g1_jac_mul(whm_j, hm, w, 2);
    G1 whm = g1_from_jac(whm_j);
    Fp12 fi;
    // committee keys are fixed per epoch: cached line coefficients
    // halve the per-entry Miller cost
    if (cache_pks) {
      miller_loop_prepared(fi, whm, *g2_prepared_cached(pks96 + 96 * i, pk));
    } else {
      G2Prepared prep;
      g2_prepare(prep, pk);
      miller_loop_prepared(fi, whm, prep);
    }
    fp12_mul(f, f, fi);
  }
  G1 agg = g1_from_jac(sig_acc);
  if (agg.inf) return 0;  // subgroup membership: per-signature above
  fp_neg(agg.y, agg.y);
  Fp12 fs, out;
  miller_loop_prepared(fs, agg, g2_generator_prepared());
  fp12_mul(f, f, fs);
  final_exponentiation(out, f);
  return fp12_eq(out, fp12_one()) ? 1 : 0;
}

// verify sig48 (compressed G1) by pk96 (compressed G2) over msg with the
// framework's hash-to-curve + DST.  Returns 1 valid / 0 invalid.
// check_pk_subgroup = 0 skips the pk r-torsion ladder — ONLY for keys
// whose membership the caller already established (e.g. an aggregate of
// individually subgroup-checked committee keys).
int hs_bls_verify_one_ex(const uint8_t *msg, size_t msg_len,
                         const uint8_t *pk96, const uint8_t *sig48,
                         int check_pk_subgroup) {
  G2 pk;
  // check_pk_subgroup==0 callers pass per-QC AGGREGATE keys: always a
  // cache miss (pure pollution) and the miss path runs the very ladder
  // the flag skips — bypass the cache for them
  if (check_pk_subgroup != 0) {
    if (!g2_from_bytes_cached(pk, pk96, true)) return 0;
  } else {
    if (!g2_from_bytes(pk, pk96, /*subgroup=*/false)) return 0;
  }
  if (pk.inf) return 0;
  G1 sig;
  if (!g1_from_bytes(sig, sig48, /*subgroup=*/true)) return 0;
  if (sig.inf) return 0;
  static const uint8_t DST[] = "HOTSTUFF_TPU_BLS_G1";
  G1 hm;
  hash_to_g1(hm, msg, msg_len, DST, sizeof(DST) - 1);
  // e(sig, G2) == e(hm, pk) via e(sig, G2) * e(-hm, pk) == 1, with
  // cached line coefficients on both fixed-G2 sides where possible
  G1 nhm = hm;
  if (!nhm.inf) fp_neg(nhm.y, nhm.y);
  Fp12 f1, f2, f, out;
  miller_loop_prepared(f1, sig, g2_generator_prepared());
  if (check_pk_subgroup != 0) {
    miller_loop_prepared(f2, nhm, *g2_prepared_cached(pk96, pk));
  } else {
    miller_loop(f2, nhm, pk);  // aggregate pk: never cache-worthy
  }
  fp12_mul(f, f1, f2);
  final_exponentiation(out, f);
  return fp12_eq(out, fp12_one()) ? 1 : 0;
}

int hs_bls_verify_one(const uint8_t *msg, size_t msg_len, const uint8_t *pk96,
                      const uint8_t *sig48) {
  return hs_bls_verify_one_ex(msg, msg_len, pk96, sig48, 1);
}

// sign msg with the secret scalar sk_le32 (32 bytes, little-endian):
// out48 = compressed x*H(msg), byte for byte what curve.py's
// hash_to_g1(msg).mul(x).to_bytes() gives, computed as the signing
// section above sets out.  Returns 1 ok / 0 for a
// scalar that is zero or not below r (the caller signs in Python).
int hs_bls_sign(const uint8_t *msg, size_t msg_len, const uint8_t *sk_le32,
                uint8_t *out48) {
  uint64_t k[4];
  for (int i = 0; i < 4; i++) {
    k[i] = 0;
    for (int b = 0; b < 8; b++)
      k[i] |= (uint64_t)sk_le32[8 * i + b] << (8 * b);
  }
  if ((k[0] | k[1] | k[2] | k[3]) == 0) return 0;
  for (int i = 3; i >= 0; i--) {
    if (k[i] < BLS_ORDER[i]) break;
    if (k[i] > BLS_ORDER[i] || i == 0) return 0;  // k >= r
  }
  static const uint8_t DST[] = "HOTSTUFF_TPU_BLS_G1";
  G1 base;
  hash_to_g1_base(base, msg, msg_len, DST, sizeof(DST) - 1);
  G1Jac hm;  // [1 - x]B, in G1
  g1_jac_mul(hm, base, &BLS_H_EFF, 1);
  uint64_t kc[4];  // sk*c mod r: the rest of h1, folded into the key
  sc_mul_small_mod_r(kc, k, BLS_H1_C);
  G1Jac sig;
  g1_glv_mul(sig, hm, kc);
  g1_to_bytes(out48, g1_from_jac(sig));
  return 1;
}

// pairing equality on uncompressed-style operands is not exposed; the
// aggregate paths reuse hs_bls_verify_one with aggregate pk/sig bytes.

// self-test hook used by the ctypes bridge at import: e(aP, bQ) == e(abP, Q)
int hs_bls_selftest(void) {
  // generator of G1 (Montgomery constants)
  G1 g1;
  fp_set(g1.x, BLS_G1X_M);
  fp_set(g1.y, BLS_G1Y_M);
  g1.inf = false;
  G2 g2 = g2_generator();
  // 5*G1, 7*G2, 35*G1
  uint64_t k5[1] = {5}, k7[1] = {7}, k35[1] = {35};
  G1Jac j5, j35;
  g1_jac_mul(j5, g1, k5, 1);
  g1_jac_mul(j35, g1, k35, 1);
  G1 p5 = g1_from_jac(j5), p35 = g1_from_jac(j35);
  G2Jac j7;
  g2_jac_mul(j7, g2, k7, 1);
  Fp2 zi, zi2, zi3;
  fp2_inv(zi, j7.z);
  fp2_sqr(zi2, zi);
  fp2_mul(zi3, zi2, zi);
  G2 q7;
  fp2_mul(q7.x, j7.x, zi2);
  fp2_mul(q7.y, j7.y, zi3);
  q7.inf = false;
  if (!pairings_equal(p5, q7, p35, g2)) return 0;
  if (pairings_equal(p5, q7, p5, g2)) return 0;  // 5*7 != 5
  return 1;
}

// ---- TPU-offload split of the distinct-digest batch (VERDICT r5 item
// 8).  The per-entry G1 scalar ladders (signature subgroup checks,
// weight multiplications, cofactor clearing) run on the TPU
// (tpu/bls.py TpuG1ScalarMul); this library provides the host ends:
// decompression/hash-to-base out, pairing product over the returned
// points back in.

// n compressed sigs -> uncompressed affine points (on-curve check
// only; subgroup membership is the DEVICE ladder's job).  1 ok.
int hs_bls_g1_decompress_many(const uint8_t *sigs48, size_t n,
                              uint8_t *out96) {
  for (size_t i = 0; i < n; i++) {
    G1 p;
    if (!g1_from_bytes(p, sigs48 + 48 * i, /*subgroup=*/false)) return 0;
    if (p.inf) return 0;  // an infinity signature proves nothing
    g1_to_uncompressed(out96 + 96 * i, p);
  }
  return 1;
}

// n 32-byte digests -> PRE-COFACTOR hash base points (the map only).
int hs_bls_hash_base_many(const uint8_t *msgs32, size_t n,
                          uint8_t *out96) {
  static const uint8_t DST[] = "HOTSTUFF_TPU_BLS_G1";
  for (size_t i = 0; i < n; i++) {
    G1 base;
    hash_to_g1_base(base, msgs32 + 32 * i, 32, DST, sizeof(DST) - 1);
    g1_to_uncompressed(out96 + 96 * i, base);
  }
  return 1;
}

// The pairing product over externally computed points: whm96[i] must be
// (r_i * h_eff) * H_base(m_i) and agg96 the sum of r_i * sig_i, both
// uncompressed affine from the device ladder (same process — the
// caller's own arithmetic, not untrusted input; on-curve is still
// checked).  Runs G + 1 prepared Miller loops + one final exp.  1 =
// accept.
int hs_bls_verify_batch_points(const uint8_t *whm96, const uint8_t *pks96,
                               size_t n, const uint8_t *agg96,
                               int check_pk_subgroup) {
  // same cache discipline as hs_bls_verify_batch: check_pk_subgroup==0
  // marks caller-validated one-shot keys that must stay out of both the
  // decode cache and the prepared-coefficient cache
  const bool cache_pks = check_pk_subgroup != 0;
  if (n == 0) return 0;
  Fp12 f = fp12_one();
  for (size_t i = 0; i < n; i++) {
    G2 pk;
    if (cache_pks) {
      if (!g2_from_bytes_cached(pk, pks96 + 96 * i, /*subgroup=*/true))
        return 0;
    } else {
      if (!g2_from_bytes(pk, pks96 + 96 * i, /*subgroup=*/false)) return 0;
    }
    if (pk.inf) return 0;
    G1 whm;
    if (!g1_from_uncompressed(whm, whm96 + 96 * i)) return 0;
    if (whm.inf) return 0;  // zero weight/hash defeats the check
    Fp12 fi;
    if (cache_pks) {
      miller_loop_prepared(fi, whm, *g2_prepared_cached(pks96 + 96 * i, pk));
    } else {
      G2Prepared prep;
      g2_prepare(prep, pk);
      miller_loop_prepared(fi, whm, prep);
    }
    fp12_mul(f, f, fi);
  }
  G1 agg;
  if (!g1_from_uncompressed(agg, agg96)) return 0;
  if (agg.inf) return 0;
  fp_neg(agg.y, agg.y);
  Fp12 fs, out;
  miller_loop_prepared(fs, agg, g2_generator_prepared());
  fp12_mul(f, f, fs);
  final_exponentiation(out, f);
  return fp12_eq(out, fp12_one()) ? 1 : 0;
}

// Stage profiler for the distinct-digest batch path (VERDICT r4 weak
// #5 / item 8): times each per-entry stage of hs_bls_verify_batch over
// `iters` synthetic entries and writes mean nanoseconds per stage to
// out_ns[5]: [0]=sig decompress+subgroup ladder, [1]=hash_to_g1,
// [2]=128-bit G1 weight mul, [3]=miller_loop, [4]=final_exponentiation
// (one-off, NOT per entry).  Committee pks are cache-decoded once per
// epoch, so g2 decompression is not a per-entry stage.
void hs_bls_profile(int iters, double *out_ns) {
  static const uint8_t DST[] = "HOTSTUFF_TPU_BLS_G1";
  using clk = std::chrono::steady_clock;
  G1 g1;
  fp_set(g1.x, BLS_G1X_M);
  fp_set(g1.y, BLS_G1Y_M);
  g1.inf = false;
  G2 g2 = g2_generator();
  uint8_t sig48[48];
  g1_to_bytes(sig48, g1);

  auto t0 = clk::now();
  for (int i = 0; i < iters; i++) {
    G1 p;
    g1_from_bytes(p, sig48, /*subgroup=*/true);
  }
  out_ns[0] = std::chrono::duration<double, std::nano>(clk::now() - t0)
                  .count() / iters;

  t0 = clk::now();
  for (int i = 0; i < iters; i++) {
    uint8_t msg[32] = {0};
    msg[0] = (uint8_t)i;
    msg[1] = (uint8_t)(i >> 8);
    G1 hm;
    hash_to_g1(hm, msg, 32, DST, sizeof(DST) - 1);
  }
  out_ns[1] = std::chrono::duration<double, std::nano>(clk::now() - t0)
                  .count() / iters;

  uint64_t w[2] = {0x0123456789abcdefULL, 0xfedcba9876543210ULL};
  t0 = clk::now();
  for (int i = 0; i < iters; i++) {
    G1Jac r;
    w[0] ^= (uint64_t)i;
    g1_jac_mul(r, g1, w, 2);
  }
  out_ns[2] = std::chrono::duration<double, std::nano>(clk::now() - t0)
                  .count() / iters;

  // the production batch path runs the PREPARED loop (cached per-epoch
  // line coefficients) — profile that, after a one-off prepare
  G2Prepared prep;
  g2_prepare(prep, g2);
  t0 = clk::now();
  Fp12 f = fp12_one();
  for (int i = 0; i < iters; i++) {
    Fp12 fi;
    miller_loop_prepared(fi, g1, prep);
    fp12_mul(f, f, fi);
  }
  out_ns[3] = std::chrono::duration<double, std::nano>(clk::now() - t0)
                  .count() / iters;

  t0 = clk::now();
  Fp12 out;
  final_exponentiation(out, f);
  out_ns[4] = std::chrono::duration<double, std::nano>(clk::now() - t0)
                  .count();
}
}

// Membership-test parity hook (tests only): xy96 = uncompressed
// big-endian affine x||y (all-zero = infinity).  use_ladder selects
// the full r-order ladder oracle vs the production endomorphism test.
// Returns 1 in-subgroup, 0 not, -1 not on the curve.
extern "C" int hs_bls_g1_membership(const uint8_t *xy96, int use_ladder) {
  G1 p;
  if (!g1_from_uncompressed(p, xy96)) return -1;
  return (use_ladder ? g1_in_subgroup_ladder(p) : g1_in_subgroup(p)) ? 1 : 0;
}
