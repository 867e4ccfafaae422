#include "ed25519.h"

#include <sys/random.h>

#include <array>
#include <atomic>
#include <cstdio>
#include <cstring>
#include <map>
#include <mutex>
#include <shared_mutex>
#include <vector>

#include "sha512.h"

namespace pbft {
namespace {

using u64 = uint64_t;
using u128 = unsigned __int128;

// ---------------------------------------------------------------------------
// GF(2^255-19), radix 2^51, limbs kept < ~2^52 between ops.
// ---------------------------------------------------------------------------

struct fe {
  u64 v[5];
};

#include "ed25519_consts.inc"

constexpr u64 kMask51 = (1ULL << 51) - 1;
constexpr fe kFeOne = {1, 0, 0, 0, 0};
constexpr fe kFeZero = {0, 0, 0, 0, 0};
// 4p limbwise (added before subtraction so limbs never underflow):
// 4*(2^51-19) and 4*(2^51-1).
constexpr u64 k4P0 = 0x1FFFFFFFFFFFB4ULL;
constexpr u64 k4P1234 = 0x1FFFFFFFFFFFFCULL;

fe fe_add(const fe& a, const fe& b) {
  fe r;
  for (int i = 0; i < 5; ++i) r.v[i] = a.v[i] + b.v[i];
  return r;
}

fe fe_sub(const fe& a, const fe& b) {
  fe r;
  r.v[0] = a.v[0] + k4P0 - b.v[0];
  r.v[1] = a.v[1] + k4P1234 - b.v[1];
  r.v[2] = a.v[2] + k4P1234 - b.v[2];
  r.v[3] = a.v[3] + k4P1234 - b.v[3];
  r.v[4] = a.v[4] + k4P1234 - b.v[4];
  return r;
}

fe fe_carry(const fe& a) {
  fe r = a;
  u64 c;
  c = r.v[0] >> 51; r.v[0] &= kMask51; r.v[1] += c;
  c = r.v[1] >> 51; r.v[1] &= kMask51; r.v[2] += c;
  c = r.v[2] >> 51; r.v[2] &= kMask51; r.v[3] += c;
  c = r.v[3] >> 51; r.v[3] &= kMask51; r.v[4] += c;
  c = r.v[4] >> 51; r.v[4] &= kMask51; r.v[0] += 19 * c;
  c = r.v[0] >> 51; r.v[0] &= kMask51; r.v[1] += c;
  return r;
}

fe fe_mul(const fe& a, const fe& b) {
  u128 t0 = (u128)a.v[0] * b.v[0] +
            (u128)(19 * a.v[1]) * b.v[4] + (u128)(19 * a.v[2]) * b.v[3] +
            (u128)(19 * a.v[3]) * b.v[2] + (u128)(19 * a.v[4]) * b.v[1];
  u128 t1 = (u128)a.v[0] * b.v[1] + (u128)a.v[1] * b.v[0] +
            (u128)(19 * a.v[2]) * b.v[4] + (u128)(19 * a.v[3]) * b.v[3] +
            (u128)(19 * a.v[4]) * b.v[2];
  u128 t2 = (u128)a.v[0] * b.v[2] + (u128)a.v[1] * b.v[1] +
            (u128)a.v[2] * b.v[0] + (u128)(19 * a.v[3]) * b.v[4] +
            (u128)(19 * a.v[4]) * b.v[3];
  u128 t3 = (u128)a.v[0] * b.v[3] + (u128)a.v[1] * b.v[2] +
            (u128)a.v[2] * b.v[1] + (u128)a.v[3] * b.v[0] +
            (u128)(19 * a.v[4]) * b.v[4];
  u128 t4 = (u128)a.v[0] * b.v[4] + (u128)a.v[1] * b.v[3] +
            (u128)a.v[2] * b.v[2] + (u128)a.v[3] * b.v[1] +
            (u128)a.v[4] * b.v[0];
  fe r;
  u128 c;
  c = t0 >> 51; r.v[0] = (u64)t0 & kMask51; t1 += c;
  c = t1 >> 51; r.v[1] = (u64)t1 & kMask51; t2 += c;
  c = t2 >> 51; r.v[2] = (u64)t2 & kMask51; t3 += c;
  c = t3 >> 51; r.v[3] = (u64)t3 & kMask51; t4 += c;
  c = t4 >> 51; r.v[4] = (u64)t4 & kMask51;
  r.v[0] += 19 * (u64)c;
  u64 c2 = r.v[0] >> 51; r.v[0] &= kMask51; r.v[1] += c2;
  return r;
}

fe fe_sq(const fe& a) {
  // Dedicated squaring: the cross terms pair up, so 15 wide multiplies
  // instead of fe_mul's 25 (~25% of scalar-mult time is squarings).
  u64 a0 = a.v[0], a1 = a.v[1], a2 = a.v[2], a3 = a.v[3], a4 = a.v[4];
  u64 d0 = 2 * a0, d1 = 2 * a1, d2 = 2 * a2, d3 = 2 * a3;
  u64 a4_19 = 19 * a4, a3_19 = 19 * a3;
  u128 t0 = (u128)a0 * a0 + (u128)d1 * a4_19 + (u128)d2 * a3_19;
  u128 t1 = (u128)d0 * a1 + (u128)d2 * a4_19 + (u128)a3_19 * a3;
  u128 t2 = (u128)d0 * a2 + (u128)a1 * a1 + (u128)d3 * a4_19;
  u128 t3 = (u128)d0 * a3 + (u128)d1 * a2 + (u128)a4_19 * a4;
  u128 t4 = (u128)d0 * a4 + (u128)d1 * a3 + (u128)a2 * a2;
  fe r;
  u128 c;
  c = t0 >> 51; r.v[0] = (u64)t0 & kMask51; t1 += c;
  c = t1 >> 51; r.v[1] = (u64)t1 & kMask51; t2 += c;
  c = t2 >> 51; r.v[2] = (u64)t2 & kMask51; t3 += c;
  c = t3 >> 51; r.v[3] = (u64)t3 & kMask51; t4 += c;
  c = t4 >> 51; r.v[4] = (u64)t4 & kMask51;
  r.v[0] += 19 * (u64)c;
  u64 c2 = r.v[0] >> 51; r.v[0] &= kMask51; r.v[1] += c2;
  return r;
}

fe fe_pow2k(fe z, int k) {
  while (k-- > 0) z = fe_sq(z);
  return z;
}

// Shared exponent chain (see pbft_tpu/crypto/field.py:_inv_chain).
void fe_chain250(const fe& z, fe* z_250_0, fe* z11) {
  fe z2 = fe_sq(z);
  fe z8 = fe_pow2k(z2, 2);
  fe z9 = fe_mul(z, z8);
  *z11 = fe_mul(z2, z9);
  fe z22 = fe_sq(*z11);
  fe z_5_0 = fe_mul(z9, z22);
  fe z_10_0 = fe_mul(fe_pow2k(z_5_0, 5), z_5_0);
  fe z_20_0 = fe_mul(fe_pow2k(z_10_0, 10), z_10_0);
  fe z_40_0 = fe_mul(fe_pow2k(z_20_0, 20), z_20_0);
  fe z_50_0 = fe_mul(fe_pow2k(z_40_0, 10), z_10_0);
  fe z_100_0 = fe_mul(fe_pow2k(z_50_0, 50), z_50_0);
  fe z_200_0 = fe_mul(fe_pow2k(z_100_0, 100), z_100_0);
  *z_250_0 = fe_mul(fe_pow2k(z_200_0, 50), z_50_0);
}

fe fe_invert(const fe& z) {  // z^(p-2) = z^(2^255 - 21)
  fe z_250_0, z11;
  fe_chain250(z, &z_250_0, &z11);
  return fe_mul(fe_pow2k(z_250_0, 5), z11);
}

fe fe_pow22523(const fe& z) {  // z^((p-5)/8) = z^(2^252 - 3)
  fe z_250_0, z11;
  fe_chain250(z, &z_250_0, &z11);
  return fe_mul(fe_pow2k(z_250_0, 2), z);
}

fe fe_canon(const fe& a) {
  fe r = fe_carry(fe_carry(a));
  // Conditionally subtract p (possibly twice; r < 2^255+eps after carries).
  // p limbs = (2^51-19, 2^51-1, 2^51-1, 2^51-1, 2^51-1).
  for (int pass = 0; pass < 2; ++pass) {
    u64 t0 = r.v[0] - (kMask51 - 18);
    u64 b = t0 >> 63;
    u64 t1 = r.v[1] - kMask51 - b;  b = t1 >> 63;
    u64 t2 = r.v[2] - kMask51 - b;  b = t2 >> 63;
    u64 t3 = r.v[3] - kMask51 - b;  b = t3 >> 63;
    u64 t4 = r.v[4] - kMask51 - b;  b = t4 >> 63;
    if (!b) {
      r.v[0] = t0 & kMask51; r.v[1] = t1 & kMask51; r.v[2] = t2 & kMask51;
      r.v[3] = t3 & kMask51; r.v[4] = t4 & kMask51;
    }
  }
  return r;
}

bool fe_eq(const fe& a, const fe& b) {
  fe x = fe_canon(a), y = fe_canon(b);
  u64 diff = 0;
  for (int i = 0; i < 5; ++i) diff |= x.v[i] ^ y.v[i];
  return diff == 0;
}

bool fe_is_zero(const fe& a) { return fe_eq(a, kFeZero); }

fe fe_neg(const fe& a) { return fe_carry(fe_sub(kFeZero, a)); }

fe fe_frombytes(const uint8_t s[32]) {
  auto load = [&](int off) {
    u64 v = 0;
    for (int i = 7; i >= 0; --i) v = (v << 8) | s[off + i];
    return v;
  };
  fe r;
  r.v[0] = load(0) & kMask51;
  r.v[1] = (load(6) >> 3) & kMask51;
  r.v[2] = (load(12) >> 6) & kMask51;
  r.v[3] = (load(19) >> 1) & kMask51;
  r.v[4] = (load(24) >> 12) & kMask51;
  return r;
}

void fe_tobytes(uint8_t s[32], const fe& a) {
  fe r = fe_canon(a);
  std::memset(s, 0, 32);
  // Pack 5x51 bits little-endian.
  u64 parts[5] = {r.v[0], r.v[1], r.v[2], r.v[3], r.v[4]};
  int bit = 0;
  for (int i = 0; i < 5; ++i) {
    for (int j = 0; j < 51; ++j) {
      if ((parts[i] >> j) & 1) s[(bit + j) / 8] |= 1u << ((bit + j) % 8);
    }
    bit += 51;
  }
}

bool fe_is_canonical_bytes(const uint8_t s[32]) {
  // y < p, with s[31]'s sign bit already masked by the caller.
  // p = 2^255 - 19: reject iff all bits 1 in [2^5..2^255) region pattern:
  u64 lo;
  std::memcpy(&lo, s, 8);
  if (lo < 0xFFFFFFFFFFFFFFEDULL) return true;
  for (int i = 8; i < 32; ++i) {
    uint8_t want = (i == 31) ? 0x7F : 0xFF;
    if (s[i] != want) return true;
  }
  return false;  // s >= p
}

// ---------------------------------------------------------------------------
// Group: extended coordinates (X:Y:Z:T), a = -1 twisted Edwards.
// ---------------------------------------------------------------------------

struct ge {
  fe x, y, z, t;
};

const ge kGeIdentity = {kFeZero, kFeOne, kFeOne, kFeZero};
const ge kGeBase = {kConst_bx, kConst_by, kFeOne, kConst_bt};

ge ge_add(const ge& p, const ge& q) {
  fe a = fe_mul(fe_carry(fe_sub(p.y, p.x)), fe_carry(fe_sub(q.y, q.x)));
  fe b = fe_mul(fe_carry(fe_add(p.y, p.x)), fe_carry(fe_add(q.y, q.x)));
  fe c = fe_mul(fe_mul(p.t, kConst_d2), q.t);
  fe zz = fe_mul(p.z, q.z);
  fe d = fe_carry(fe_add(zz, zz));
  fe e = fe_carry(fe_sub(b, a));
  fe f = fe_carry(fe_sub(d, c));
  fe g = fe_carry(fe_add(d, c));
  fe h = fe_carry(fe_add(b, a));
  return {fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h)};
}

ge ge_dbl(const ge& p) {
  // Dedicated doubling (dbl-2008-hwcd, a = -1): 4M + 4S vs the unified
  // add's 9M — scalar ladders are doubling-dominated, so this is the
  // single biggest lever on sign/verify latency. Mirrors the JAX
  // point_double (pbft_tpu/crypto/ed25519.py) formula for formula-level
  // parity between the runtimes.
  fe a = fe_sq(p.x);
  fe b = fe_sq(p.y);
  fe zz = fe_sq(p.z);
  fe c = fe_carry(fe_add(zz, zz));
  fe xy = fe_carry(fe_add(p.x, p.y));
  fe e = fe_carry(fe_sub(fe_carry(fe_sub(fe_sq(xy), a)), b));
  fe d = fe_neg(a);  // a = -1 twist
  fe g = fe_carry(fe_add(d, b));
  fe f = fe_carry(fe_sub(g, c));
  fe h = fe_carry(fe_sub(d, b));
  return {fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h)};
}

ge ge_neg(const ge& p) { return {fe_neg(p.x), p.y, p.z, fe_neg(p.t)}; }

bool ge_decompress(ge* out, const uint8_t bytes[32]) {
  uint8_t s[32];
  std::memcpy(s, bytes, 32);
  int sign = s[31] >> 7;
  s[31] &= 0x7F;
  if (!fe_is_canonical_bytes(s)) return false;
  fe y = fe_frombytes(s);
  fe y2 = fe_sq(y);
  fe u = fe_carry(fe_sub(y2, kFeOne));
  fe v = fe_carry(fe_add(fe_mul(y2, kConst_d), kFeOne));
  // x = u v^3 (u v^7)^((p-5)/8), corrected by sqrt(-1) when needed.
  fe v3 = fe_mul(v, fe_sq(v));
  fe v7 = fe_mul(v3, fe_sq(fe_sq(v)));
  fe x = fe_mul(fe_mul(u, v3), fe_pow22523(fe_mul(u, v7)));
  fe check = fe_mul(v, fe_sq(x));
  if (!fe_eq(check, u)) {
    if (fe_eq(check, fe_neg(u))) {
      x = fe_mul(x, kConst_sqrtm1);
    } else {
      return false;
    }
  }
  x = fe_canon(x);
  bool x_zero = fe_is_zero(x);
  if (x_zero && sign) return false;
  if ((int)(x.v[0] & 1) != sign) x = fe_neg(x);
  out->x = x;
  out->y = y;
  out->z = kFeOne;
  out->t = fe_mul(x, y);
  return true;
}

void ge_compress(uint8_t s[32], const ge& p) {
  fe zi = fe_invert(p.z);
  fe x = fe_canon(fe_mul(p.x, zi));
  fe y = fe_mul(p.y, zi);
  fe_tobytes(s, y);
  s[31] |= (uint8_t)((x.v[0] & 1) << 7);
}

// ---------------------------------------------------------------------------
// Scalars mod L = 2^252 + delta.
// ---------------------------------------------------------------------------

constexpr u64 kL[4] = {0x5812631a5cf5d3edULL, 0x14def9dea2f79cd6ULL, 0ULL,
                       0x1000000000000000ULL};
// floor(2^512 / L), 260 bits: sc_reduce512's Barrett reciprocal.
constexpr u64 kMu[5] = {0xed9ce5a30a2c131bULL, 0x2106215d086329a7ULL,
                        0xffffffffffffffebULL, 0xffffffffffffffffULL, 0xfULL};

// out = a * b, 64-bit LE limbs: na + nb of them, all written.
inline void limbs_mul(u64* out, const u64* a, int na, const u64* b, int nb) {
  for (int j = 0; j < nb; ++j) out[j] = 0;
  for (int i = 0; i < na; ++i) {
    u128 carry = 0;
    for (int j = 0; j < nb; ++j) {
      u128 cur = (u128)out[i + j] + (u128)a[i] * b[j] + carry;
      out[i + j] = (u64)cur;
      carry = cur >> 64;
    }
    out[i + nb] = (u64)carry;
  }
}

// out = a - b over four limbs; returns the borrow out of the top one.
inline u64 limbs_sub4(u64 out[4], const u64 a[4], const u64 b[4]) {
  u64 borrow = 0;
  for (int i = 0; i < 4; ++i) {
    u128 d = (u128)a[i] - b[i] - borrow;
    out[i] = (u64)d;
    borrow = (u64)(d >> 64) & 1;
  }
  return borrow;
}

// x -= L when that keeps x >= 0 (x: 4 limbs), by a mask: no branch on
// the value.
inline void sc_sub_l_if_ge(u64 x[4]) {
  u64 t[4];
  const u64 keep = 0 - limbs_sub4(t, x, kL);  // all ones when x < L
  for (int i = 0; i < 4; ++i) x[i] = (x[i] & keep) | (t[i] & ~keep);
}

// 512-bit (8 limb) value -> 256-bit scalar mod L (4 limbs). Barrett
// reduction (Handbook of Applied Cryptography 14.42) in base b = 2^64 with
// k = 4 limbs (b^3 <= L < b^4): q3 = floor(floor(x / b^3) * mu / b^5) is
// floor(x / L) or up to 2 under it for every x < b^8, so r = x - q3 * L
// lies in [0, 3L). 3L < 2^254, so r is exact in its low four limbs (the
// higher limbs of x and of q3 * L cancel: q3's fifth limb is not even
// multiplied), and two conditional subtractions of L finish. A 5x5 and a
// 4x4 limb product, whatever the value.
void sc_reduce512(u64 out[4], const u64 in[8]) {
  u64 q2[10], ql[8];
  limbs_mul(q2, in + 3, 5, kMu, 5);
  limbs_mul(ql, q2 + 5, 4, kL, 4);  // q3 * L; its low four limbs are used
  limbs_sub4(out, in, ql);
  sc_sub_l_if_ge(out);
  sc_sub_l_if_ge(out);
}

bool sc_lt_l(const u64 s[4]) {
  for (int i = 3; i >= 0; --i) {
    if (s[i] < kL[i]) return true;
    if (s[i] > kL[i]) return false;
  }
  return false;
}

void sc_from_bytes(u64 out[4], const uint8_t b[32]) {
  std::memcpy(out, b, 32);  // little-endian host
}

void sc_to_bytes(uint8_t out[32], const u64 s[4]) { std::memcpy(out, s, 32); }

// (a*b + c) mod L, a of na <= 4 limbs, b and c of 4. The intermediate is
// at most (2^256 - 1)^2 + 2^256 - 1 < 2^512: it fits sc_reduce512's eight
// limbs for any operands, reduced or not.
void sc_muladd_limbs(u64 out[4], const u64* a, int na, const u64 b[4],
                     const u64 c[4]) {
  u64 wide[8] = {0};
  limbs_mul(wide, a, na, b, 4);
  u64 carry = 0;
  for (int i = 0; i < 8; ++i) {
    u128 cur = (u128)wide[i] + (i < 4 ? c[i] : 0) + carry;
    wide[i] = (u64)cur;
    carry = (u64)(cur >> 64);
  }
  sc_reduce512(out, wide);
}

// (a*b + c) mod L for signing.
void sc_muladd(u64 out[4], const u64 a[4], const u64 b[4], const u64 c[4]) {
  sc_muladd_limbs(out, a, 4, b, c);
}

// (a*b + c) mod L with a < 2^128 (the batch-verification coefficient
// path, three times per batched item): half the product's multiplications;
// a*b + c < 2^384 + 2^256 goes through the same reduction.
void sc_muladd128(u64 out[4], const u64 a[2], const u64 b[4],
                  const u64 c[4]) {
  sc_muladd_limbs(out, a, 2, b, c);
}

// (a + b) mod L, both inputs < L: the sum is under 2L < 2^254, four limbs
// and one conditional subtraction.
void sc_add(u64 out[4], const u64 a[4], const u64 b[4]) {
  u64 carry = 0;
  for (int i = 0; i < 4; ++i) {
    u128 cur = (u128)a[i] + b[i] + carry;
    out[i] = (u64)cur;
    carry = (u64)(cur >> 64);
  }
  sc_sub_l_if_ge(out);
}

// ---------------------------------------------------------------------------
// High level.
// ---------------------------------------------------------------------------

// acc = [s1]B + [s2]Q, Shamir/Straus with a joint 2-bit window: 128
// iterations of (2 dedicated doublings + at most 1 addition) over the
// 16-entry table E[s + 4h] = [s]B + [h]Q — the same shape as the JAX
// shamir_ladder (pbft_tpu/crypto/ed25519.py), ~40% fewer point ops than
// the per-bit form.
ge double_scalar_mult(const u64 s1[4], const ge& q, const u64 s2[4]) {
  ge b2 = ge_dbl(kGeBase);
  ge rowb[4] = {kGeIdentity, kGeBase, b2, ge_add(b2, kGeBase)};
  ge q2 = ge_dbl(q);
  ge rowq[4] = {kGeIdentity, q, q2, ge_add(q2, q)};
  ge table[16];
  for (int h = 0; h < 4; ++h)
    for (int s = 0; s < 4; ++s)
      table[4 * h + s] = h == 0   ? rowb[s]
                         : s == 0 ? rowq[h]
                                  : ge_add(rowb[s], rowq[h]);
  ge acc = kGeIdentity;
  for (int w = 127; w >= 0; --w) {
    acc = ge_dbl(ge_dbl(acc));
    int shift = (2 * w) % 64;  // bit pair never straddles a word (even bit)
    int s = (s1[w >> 5] >> shift) & 3;
    int h = (s2[w >> 5] >> shift) & 3;
    int idx = s | (h << 2);
    if (idx) acc = ge_add(acc, table[idx]);
  }
  return acc;
}

// kComb[i][v] = [v * 2^(8i)]B: fixed-base scalar multiplication as 31
// table additions and zero doublings. ~1.3 MB, built once on first use
// (~8k additions, a few ms); the multiplication goes from a full ladder to
// ~10 us, which with ge_compress's inversion (~6 us) is nearly all of a
// signature (core_test prints what one costs on the host at hand).
const ge* comb_table() {
  static const std::vector<ge> t = [] {
    std::vector<ge> v(32 * 256);
    ge base = kGeBase;  // [2^(8i)]B for the current row
    for (int i = 0; i < 32; ++i) {
      v[i * 256] = kGeIdentity;
      for (int j = 1; j < 256; ++j) v[i * 256 + j] = ge_add(v[i * 256 + j - 1], base);
      base = ge_dbl(v[i * 256 + 128]);  // [2^(8(i+1))]B = 2 * [128 * 2^(8i)]B
    }
    return v;
  }();
  return t.data();
}

ge scalar_mult_base(const u64 s[4]) {
  const ge* t = comb_table();
  ge acc = kGeIdentity;
  for (int i = 0; i < 32; ++i) {
    int byte = (int)((s[i / 8] >> (8 * (i % 8))) & 0xFF);
    if (byte) acc = ge_add(acc, t[i * 256 + byte]);
  }
  return acc;
}

void expand_seed(u64 a_sc[4], uint8_t prefix[32], const uint8_t seed[32]) {
  uint8_t h[64];
  sha512(h, seed, 32);
  h[0] &= 248;
  h[31] &= 127;
  h[31] |= 64;
  sc_from_bytes(a_sc, h);
  std::memcpy(prefix, h + 32, 32);
}

void hash_to_scalar(u64 out[4], const uint8_t* p1, const uint8_t* p2,
                    const uint8_t* p3, size_t n3) {
  // SHA512(p1 || p2 || p3) mod L, p1/p2 32 bytes each (or p2 null). What
  // the protocol signs is a 32-byte digest, 64 or 96 bytes here: the
  // stack. The message length is caller-controlled (public C ABI): the
  // heap beyond that.
  const size_t head = p2 ? 64 : 32, n = head + n3;
  uint8_t stack[128];
  std::vector<uint8_t> heap;
  uint8_t* buf = stack;
  if (n > sizeof(stack)) {
    heap.resize(n);
    buf = heap.data();
  }
  std::memcpy(buf, p1, 32);
  if (p2) std::memcpy(buf + 32, p2, 32);
  if (n3) std::memcpy(buf + head, p3, n3);
  uint8_t h[64];
  sha512(h, buf, n);
  u64 wide[8];
  std::memcpy(wide, h, 64);
  sc_reduce512(out, wide);
}

}  // namespace

void ed25519_public_key(uint8_t pub[32], const uint8_t seed[32]) {
  u64 a[4];
  uint8_t prefix[32];
  expand_seed(a, prefix, seed);
  ge p = scalar_mult_base(a);
  ge_compress(pub, p);
}

// NOT constant-time (comb lookups index by secret bytes, zero digits skip
// the addition): fine for this framework, where each replica signs public
// protocol messages with a per-process key on hardware it owns, but do
// not lift this into a context with co-resident adversaries.
void ed25519_sign(uint8_t sig[64], const uint8_t seed[32], const uint8_t* msg,
                  size_t msglen) {
  // A replica signs every outgoing protocol message with ONE seed for the
  // process lifetime (core/replica.cc), so the expanded secret scalar,
  // prefix, and public key are cached — recomputing them was ~1/3 of the
  // per-sign cost (two SHA-512s + a comb mult + a field inversion).
  struct Expanded {
    uint8_t seed[32];
    u64 a[4];
    uint8_t prefix[32];
    uint8_t pub[32];
    bool valid = false;
  };
  thread_local Expanded cache;
  if (!cache.valid || std::memcmp(cache.seed, seed, 32) != 0) {
    expand_seed(cache.a, cache.prefix, seed);
    ge p = scalar_mult_base(cache.a);
    ge_compress(cache.pub, p);
    std::memcpy(cache.seed, seed, 32);
    cache.valid = true;
  }
  u64 r[4];
  hash_to_scalar(r, cache.prefix, nullptr, msg, msglen);
  ge rp = scalar_mult_base(r);
  uint8_t rbytes[32];
  ge_compress(rbytes, rp);
  u64 h[4];
  hash_to_scalar(h, rbytes, cache.pub, msg, msglen);
  u64 s[4];
  sc_muladd(s, h, cache.a, r);
  std::memcpy(sig, rbytes, 32);
  sc_to_bytes(sig + 32, s);
}

// --- Ephemeral Diffie-Hellman on edwards25519 (core/secure.cc handshake).
// X25519-style clamping clears the cofactor (the scalar is a multiple of
// 8), so a small-order peer point collapses to the identity and is
// rejected instead of zeroing the key contribution.

namespace {
void dh_clamp(uint8_t clamped[32], const uint8_t secret[32]) {
  std::memcpy(clamped, secret, 32);
  clamped[0] &= 248;
  clamped[31] &= 127;
  clamped[31] |= 64;
}
constexpr uint8_t kIdentityEnc[32] = {1};  // compressed identity: y = 1
}  // namespace

void ed25519_dh_public(uint8_t pub[32], const uint8_t secret[32]) {
  uint8_t clamped[32];
  dh_clamp(clamped, secret);
  u64 k[4];
  sc_from_bytes(k, clamped);
  ge_compress(pub, scalar_mult_base(k));
}

bool ed25519_dh_shared(uint8_t out[32], const uint8_t secret[32],
                       const uint8_t peer_pub[32]) {
  ge p;
  if (!ge_decompress(&p, peer_pub)) return false;
  uint8_t clamped[32];
  dh_clamp(clamped, secret);
  // Plain double-and-add (handshakes are once per connection; no need for
  // the comb/Shamir machinery here).
  ge acc = kGeIdentity;
  for (int i = 255; i >= 0; --i) {
    acc = ge_dbl(acc);
    if ((clamped[i >> 3] >> (i & 7)) & 1) acc = ge_add(acc, p);
  }
  ge_compress(out, acc);
  return std::memcmp(out, kIdentityEnc, 32) != 0;
}

bool ed25519_verify(const uint8_t pub[32], const uint8_t* msg, size_t msglen,
                    const uint8_t sig[64]) {
  ge a;
  if (!ge_decompress(&a, pub)) return false;
  u64 s[4];
  sc_from_bytes(s, sig + 32);
  if (!sc_lt_l(s)) return false;
  u64 h[4];
  hash_to_scalar(h, sig, pub, msg, msglen);
  ge p = double_scalar_mult(s, ge_neg(a), h);  // [S]B + [h](-A)
  uint8_t enc[32];
  ge_compress(enc, p);
  return std::memcmp(enc, sig, 32) == 0;
}

// ---------------------------------------------------------------------------
// Batch verification: random-linear-combination check + Pippenger MSM.
//
// A batch is split into FIXED windows of kEd25519RlcWindowItems — the
// window composition depends only on item order, so the serial loop here
// and the parallel per-window dispatch in core/verify_pool.cc produce the
// same accept set at every thread count. A window of n signatures is
// checked as
//     [sum z_i S_i] B  ==  sum [z_i] R_i + sum [z_i h_i] A_i
// with fresh random 128-bit z_i. All honest windows pass with one
// multi-scalar multiplication over 2n points — asymptotically ~253/w
// doublings plus (2n + 2^(w+1)) additions per w-bit digit column, vs the
// ~256 doublings + ~96 additions EACH of n independent Shamir ladders —
// and any failing window bisects down to per-item ed25519_verify, which
// stays the authority for every rejected item ("batch-reject path must
// not stall rounds", BASELINE config 5).
//
// Accept-set note (documented, tested in tests/test_native_crypto.py):
// per-item semantics are cofactorless. The batch check weights defects
// by z_i; z_i === 1 (mod 8) forces any SINGLE small-order (torsion)
// defect to survive the combination, so a lone crafted signature is
// still rejected deterministically. A signer who crafts TWO signatures
// with cancelling torsion defects can get the pair accepted when both
// land in one window — replicas with different window compositions may
// then disagree about those two signatures. That grants the adversary
// nothing new: a Byzantine signer can already produce per-replica
// disagreement by sending different bytes to different replicas
// (equivocation), which PBFT's quorum intersection tolerates by design.
//
// Entropy exhaustion: if no entropy source answers, the RLC fast path is
// DISABLED and the window verifies per-item (predictable z_i would let a
// crafted cancelling-defect pair pass the combination — ADVICE round-5).
// ---------------------------------------------------------------------------

namespace {

std::atomic<bool> g_force_entropy_exhaustion{false};

// Fill buf with n random bytes for RLC coefficients. Returns false when
// no entropy source answers (ADVICE round-5 medium): the old last-resort
// — a per-process counter hashed through SHA-512 — was PREDICTABLE, and
// an attacker who predicts z_i can craft two invalid signatures with
// cancelling non-torsion defects that pass the RLC check without the
// bisect ever running. On failure the caller must disable the fast path
// and verify the window per-item (core/secure.cc fill_random treats the
// same condition as fatal; verification has a sound slow path, so it
// degrades instead).
bool batch_coeffs_random(uint8_t* buf, size_t n) {
  if (g_force_entropy_exhaustion.load(std::memory_order_relaxed)) return false;
  size_t off = 0;
  int failures = 0;
  while (off < n) {
    ssize_t r = getrandom(buf + off, n - off, 0);
    if (r > 0) {
      off += (size_t)r;
      continue;
    }
    // getrandom unavailable/interrupted: /dev/urandom next (same tiering
    // as core/secure.cc fill_random).
    if (FILE* f = std::fopen("/dev/urandom", "rb")) {
      size_t got = std::fread(buf + off, 1, n - off, f);
      std::fclose(f);
      off += got;
      if (got > 0) continue;
    }
    if (++failures > 16) return false;
  }
  return true;
}

// Pippenger bucket MSM: sum [scalars[i]] pts[i], scalars 4-limb < L.
int msm_window_bits(size_t m) {
  if (m < 64) return 3;
  if (m < 256) return 5;
  if (m < 1024) return 6;
  return 8;
}

ge msm_pippenger(const std::vector<ge>& pts,
                 const std::vector<std::array<u64, 4>>& scalars) {
  const int w = msm_window_bits(pts.size());
  const int nbuckets = (1 << w) - 1;
  std::vector<ge> buckets(nbuckets);
  std::vector<uint8_t> used(nbuckets);
  const int positions = (253 + w - 1) / w;
  ge acc = kGeIdentity;
  for (int pos = positions - 1; pos >= 0; --pos) {
    for (int k = 0; k < w; ++k) acc = ge_dbl(acc);
    std::fill(used.begin(), used.end(), 0);
    const int bit0 = pos * w;
    const int limb = bit0 >> 6, off = bit0 & 63;
    for (size_t i = 0; i < pts.size(); ++i) {
      const u64* s = scalars[i].data();
      u64 digit = s[limb] >> off;
      if (off + w > 64 && limb + 1 < 4) digit |= s[limb + 1] << (64 - off);
      digit &= (u64)nbuckets;
      if (!digit) continue;
      // First hit assigns (an add against the identity is a full point
      // addition — pure waste at ~9 field muls a pop).
      if (used[digit - 1]) {
        buckets[digit - 1] = ge_add(buckets[digit - 1], pts[i]);
      } else {
        buckets[digit - 1] = pts[i];
        used[digit - 1] = 1;
      }
    }
    // sum_d (d+1)*buckets[d] via suffix sums, skipping identity work.
    bool have_run = false, have_col = false;
    ge running, colsum;
    for (int d = nbuckets - 1; d >= 0; --d) {
      if (used[d]) {
        running = have_run ? ge_add(running, buckets[d]) : buckets[d];
        have_run = true;
      }
      if (have_run) {
        colsum = have_col ? ge_add(colsum, running) : running;
        have_col = true;
      }
    }
    if (have_col) acc = ge_add(acc, colsum);
  }
  return acc;
}

// Per-key decompressed-point cache for window prep: a replica verifies
// against a tiny, stable key set (n replica identities + a handful of
// clients), so the pubkey decompression — a field inverse-sqrt
// exponentiation per item — is almost always redundant. Keyed by the 32
// raw pubkey bytes; negative results (non-canonical / off-curve keys)
// are cached too, and ge_decompress is deterministic, so this is pure
// memoization — the accept set cannot move (parity pinned by
// tests/test_verify_pool.py against the cold path). Shared by every
// pool worker: hits take a shared lock, first-sight inserts the
// exclusive lock; at the (generous) bound the map is cleared outright —
// the working set is orders of magnitude smaller.
struct PubkeyCacheEntry {
  ge pt;
  bool valid;
};
std::shared_mutex g_pubkey_cache_mu;
std::map<std::array<uint8_t, 32>, PubkeyCacheEntry> g_pubkey_cache;
std::atomic<bool> g_pubkey_cache_disabled{false};
constexpr size_t kPubkeyCacheMax = 1024;

bool cached_decompress_pubkey(ge* out, const uint8_t pub[32]) {
  if (g_pubkey_cache_disabled.load(std::memory_order_relaxed)) {
    return ge_decompress(out, pub);
  }
  std::array<uint8_t, 32> key;
  std::memcpy(key.data(), pub, 32);
  {
    std::shared_lock<std::shared_mutex> lk(g_pubkey_cache_mu);
    auto it = g_pubkey_cache.find(key);
    if (it != g_pubkey_cache.end()) {
      if (it->second.valid) *out = it->second.pt;
      return it->second.valid;
    }
  }
  PubkeyCacheEntry e;
  e.valid = ge_decompress(&e.pt, pub);
  {
    std::unique_lock<std::shared_mutex> lk(g_pubkey_cache_mu);
    if (g_pubkey_cache.size() >= kPubkeyCacheMax) g_pubkey_cache.clear();
    g_pubkey_cache.emplace(key, e);
  }
  if (e.valid) *out = e.pt;
  return e.valid;
}

// Per-item state shared by the RLC fast path and the bisect fallback
// (only items whose decompressions + S<L pre-checks passed are prepared;
// the `live` index set tracks exactly those).
struct BatchPrep {
  ge a;  // decompressed public key
  ge r;  // decompressed R (canonical-encoding check included)
  u64 s[4];
  u64 h[4];
};

bool ge_points_equal(const ge& p, const ge& q) {
  uint8_t ep[32], eq[32];
  ge_compress(ep, p);
  ge_compress(eq, q);
  return std::memcmp(ep, eq, 32) == 0;
}

// Per-item slow path over prepared items — the authority for every
// rejection, and the whole path when entropy is unavailable.
void verify_prepared_per_item(const std::vector<BatchPrep>& prep,
                              const std::vector<size_t>& idx, uint8_t* out) {
  for (size_t i : idx) {
    const BatchPrep& it = prep[i];
    ge p = double_scalar_mult(it.s, ge_neg(it.a), it.h);
    out[i] = ge_points_equal(p, it.r) ? 1 : 0;
  }
}

enum class RlcResult { kPass, kFail, kNoEntropy };

// One RLC check over the subset `idx` of prepared items; fresh z_i per
// call (bisect recursion re-randomizes).
RlcResult rlc_check(const std::vector<BatchPrep>& prep,
                    const std::vector<size_t>& idx) {
  const size_t n = idx.size();
  std::vector<uint8_t> rnd(16 * n);
  if (!batch_coeffs_random(rnd.data(), rnd.size())) {
    return RlcResult::kNoEntropy;
  }
  std::vector<ge> pts;
  std::vector<std::array<u64, 4>> scalars;
  pts.reserve(2 * n);
  scalars.reserve(2 * n);
  u64 sb[4] = {0};
  for (size_t k = 0; k < n; ++k) {
    const BatchPrep& it = prep[idx[k]];
    u64 z[4] = {0, 0, 0, 0};
    std::memcpy(z, rnd.data() + 16 * k, 16);
    // z === 1 (mod 8): a lone torsion defect cannot cancel (see note).
    z[0] = (z[0] & ~7ULL) | 1;
    u64 zero[4] = {0}, zs[4], zh[4];
    sc_muladd128(zs, z, it.s, zero);
    sc_muladd128(zh, z, it.h, zero);
    sc_add(sb, sb, zs);  // sb += z_i * S_i (mod L)
    pts.push_back(it.r);
    scalars.push_back({z[0], z[1], z[2], z[3]});
    pts.push_back(it.a);
    scalars.push_back({zh[0], zh[1], zh[2], zh[3]});
  }
  return ge_points_equal(scalar_mult_base(sb), msm_pippenger(pts, scalars))
             ? RlcResult::kPass
             : RlcResult::kFail;
}

void batch_bisect(const std::vector<BatchPrep>& prep,
                  const std::vector<size_t>& idx, uint8_t* out) {
  // Below the crossover the MSM costs more than independent ladders;
  // the per-item equation reuses the prepared points (R was decompressed
  // from a canonical encoding, so point equality == the byte compare
  // ed25519_verify does).
  if (idx.size() < 8) {
    verify_prepared_per_item(prep, idx, out);
    return;
  }
  switch (rlc_check(prep, idx)) {
    case RlcResult::kPass:
      for (size_t i : idx) out[i] = 1;
      return;
    case RlcResult::kNoEntropy:
      // No unpredictable coefficients: the fast path is unsound (see
      // batch_coeffs_random). Per-item verification needs no randomness.
      verify_prepared_per_item(prep, idx, out);
      return;
    case RlcResult::kFail:
      break;
  }
  std::vector<size_t> lo(idx.begin(), idx.begin() + idx.size() / 2);
  std::vector<size_t> hi(idx.begin() + idx.size() / 2, idx.end());
  batch_bisect(prep, lo, out);
  batch_bisect(prep, hi, out);
}

}  // namespace

void ed25519_test_force_entropy_exhaustion(bool on) {
  g_force_entropy_exhaustion.store(on, std::memory_order_relaxed);
}

void ed25519_test_sc_reduce512(uint8_t out[32], const uint8_t in[64]) {
  u64 x[8], r[4];
  std::memcpy(x, in, 64);
  sc_reduce512(r, x);
  sc_to_bytes(out, r);
}

void ed25519_test_sc_muladd(uint8_t out[32], const uint8_t a[32],
                            const uint8_t b[32], const uint8_t c[32]) {
  u64 x[4], y[4], z[4], r[4];
  sc_from_bytes(x, a);
  sc_from_bytes(y, b);
  sc_from_bytes(z, c);
  sc_muladd(r, x, y, z);
  sc_to_bytes(out, r);
}

void ed25519_test_sc_muladd128(uint8_t out[32], const uint8_t a[16],
                               const uint8_t b[32], const uint8_t c[32]) {
  u64 x[2], y[4], z[4], r[4];
  std::memcpy(x, a, 16);
  sc_from_bytes(y, b);
  sc_from_bytes(z, c);
  sc_muladd128(r, x, y, z);
  sc_to_bytes(out, r);
}

void ed25519_test_sc_add(uint8_t out[32], const uint8_t a[32],
                         const uint8_t b[32]) {
  u64 x[4], y[4], r[4];
  sc_from_bytes(x, a);
  sc_from_bytes(y, b);
  sc_add(r, x, y);
  sc_to_bytes(out, r);
}

void ed25519_pubkey_cache_clear() {
  std::unique_lock<std::shared_mutex> lk(g_pubkey_cache_mu);
  g_pubkey_cache.clear();
}

void ed25519_test_pubkey_cache_disable(bool on) {
  g_pubkey_cache_disabled.store(on, std::memory_order_relaxed);
  if (on) ed25519_pubkey_cache_clear();
}

void ed25519_verify_window(const uint8_t* pubs, const uint8_t* msgs,
                           const uint8_t* sigs, size_t n, uint8_t* out) {
  if (n < 8) {
    // Below the RLC crossover the independent ladders win — and the
    // prep work (two decompressions + the hash per item) would only be
    // thrown away, since the per-item path recomputes it.
    for (size_t i = 0; i < n; ++i) {
      out[i] = ed25519_verify(pubs + 32 * i, msgs + 32 * i, 32, sigs + 64 * i)
                   ? 1
                   : 0;
    }
    return;
  }
  std::vector<BatchPrep> prep(n);
  std::vector<size_t> live;
  live.reserve(n);
  // Pipelined prep: one pass of pure SHA-512 hashing first (sequential,
  // branch-light, keeps the compression function hot in I-cache), then a
  // pass of point decompressions + scalar pre-checks. The split costs
  // nothing on the honest path and lets each loop stay in its own
  // working set instead of ping-ponging between hash and field code.
  for (size_t i = 0; i < n; ++i) {
    out[i] = 0;
    hash_to_scalar(prep[i].h, sigs + 64 * i, pubs + 32 * i, msgs + 32 * i, 32);
  }
  for (size_t i = 0; i < n; ++i) {
    BatchPrep& it = prep[i];
    if (!cached_decompress_pubkey(&it.a, pubs + 32 * i)) continue;
    // R must be a canonical curve-point encoding: the per-item check
    // compares encode([S]B - [h]A) against the R bytes, and encode()
    // only emits canonical encodings — ge_decompress accepts exactly
    // that image, so decompression preserves the accept set.
    if (!ge_decompress(&it.r, sigs + 64 * i)) continue;
    sc_from_bytes(it.s, sigs + 64 * i + 32);
    if (!sc_lt_l(it.s)) continue;
    live.push_back(i);
  }
  batch_bisect(prep, live, out);
}

void ed25519_verify_batch(const uint8_t* pubs, const uint8_t* msgs,
                          const uint8_t* sigs, size_t n, uint8_t* out) {
  for (size_t off = 0; off < n; off += kEd25519RlcWindowItems) {
    size_t w = n - off < kEd25519RlcWindowItems ? n - off
                                                : kEd25519RlcWindowItems;
    ed25519_verify_window(pubs + 32 * off, msgs + 32 * off, sigs + 64 * off,
                          w, out + off);
  }
}

}  // namespace pbft
