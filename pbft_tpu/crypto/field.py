"""GF(2^255-19) and mod-L arithmetic in JAX, designed for vmap/XLA on TPU.

Representation: field elements are (..., 32) **int32** arrays of 8-bit limbs,
little-endian (value = sum limb_i * 2^(8*i)) — the radix is chosen for the
TPU's 32-bit vector unit: every op is native int32, no jax x64 mode and no
emulated 64-bit arithmetic anywhere. A pleasant consequence of radix 2^8 is
that the canonical byte encoding and the limb array coincide, so
``bytes_to_limbs``/``limbs_to_bytes`` are casts, not repacks.

Limbs are *signed* and allowed to drift above 8 bits between operations
("loose" form); every multiply renormalizes. The signed-limb choice makes
subtraction carry-free and the arithmetic right shift does borrow
propagation for free.

Bounds that make this sound (see ``mul``): a carried limb is < 2^8 + 38,
and every mul input is a sum/difference of at most 4 carried values (the
point formulas in ed25519.py never nest deeper), so |limb| < 4*(2^8+38)
< 2^10.3. Schoolbook columns are then < 32 * 2^20.6 = 2^25.6 and the
38-fold (2^256 = 38 mod p) keeps every intermediate < 39 * 2^25.6 < 2^30.9
— inside int32. Two carry passes return limbs to carried form. The
``tests/test_field.py`` hostile-bounds test pins this window.

The mod-L half (group order L = 2^252 + delta) implements the 512-bit
challenge-hash reduction with three positivity-preserving folds at the 2^252
boundary: x = hi*2^252 + lo == lo - hi*delta + M_k*L (mod L) where M_k is a
static per-iteration constant chosen so the result stays non-negative while
still shrinking ~127 bits per fold.

This is the arithmetic layer under pbft_tpu.crypto.ed25519; everything here
is batch-agnostic (leading dims broadcast) and contains no data-dependent
control flow, so it jits and vmaps cleanly onto TPU.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
DELTA = L - 2**252
NLIMBS = 32
RADIX = 8
MASK = 0xFF

_DTYPE = jnp.int32


def limbs_const(v: int, n: int = NLIMBS) -> np.ndarray:
    """Static Python int -> (n,) int32 limb array (8-bit, little-endian)."""
    return np.array(
        [(v >> (RADIX * i)) & MASK for i in range(n)], dtype=np.int32
    )


def limbs_to_int(arr) -> int:
    """(…,32) limbs -> Python int (tests/debug only; takes the last axis)."""
    a = np.asarray(arr, dtype=object)
    return int(sum(int(x) << (RADIX * i) for i, x in enumerate(a)))


_P_LIMBS = limbs_const(P)
_2P_LIMBS = limbs_const(2 * P)


def zeros_like_field(x):
    return jnp.zeros(x.shape, _DTYPE)


def carry_seq(x):
    """One exact sequential carry pass; wraps the 2^256 overflow back as
    *38 (mod p). Produces limbs in [0, 2^8) except limb 0, which keeps a
    small fold residue. Used by canon(), whose conditional subtracts need
    exact byte-range limbs; the hot path uses the vectorized ``carry``."""
    out = []
    c = jnp.zeros_like(x[..., 0])
    for i in range(NLIMBS):
        v = x[..., i] + c
        c = v >> RADIX
        out.append(v & MASK)
    r = jnp.stack(out, axis=-1)
    return r.at[..., 0].add(38 * c)


def carry(x, passes: int = 2):
    """Vectorized carry: each pass splits every limb into (low byte, carry)
    simultaneously and shifts the carries up one position — wide (…,32)
    vector ops instead of a 32-step sequential chain, which keeps the XLA
    graph ~5x smaller and maps onto the TPU VPU as a handful of fused
    elementwise ops. The carry leaving limb 31 re-enters limb 0 as *38
    (2^256 = 38 mod p).

    Convergence ("carried" = limbs in (-2^9, 2^9)): 2 passes suffice for
    sums/differences of carried values; 4 passes for mul's folded columns
    (|col| < 2^28.3 -> < 2^25.6 -> ~2^16 -> ~2^13 -> < 2^8 + 38). All
    intermediates stay far inside int32.
    """
    for _ in range(passes):
        lo = x & MASK
        hi = x >> RADIX  # arithmetic shift: exact floor even for negatives
        x = lo + jnp.concatenate(
            [38 * hi[..., NLIMBS - 1 :], hi[..., : NLIMBS - 1]], axis=-1
        )
    return x


def add(a, b):
    return carry(a + b)


def sub(a, b):
    return carry(a - b)


def neg(a):
    return carry(jnp.asarray(_2P_LIMBS) - a)


def _mul_schoolbook(a, b):
    """Shifted-accumulate schoolbook: best lowering on XLA:CPU."""
    cols = jnp.zeros(
        jnp.broadcast_shapes(a.shape, b.shape)[:-1] + (2 * NLIMBS - 1,), _DTYPE
    )
    for i in range(NLIMBS):
        cols = cols.at[..., i : i + NLIMBS].add(a[..., i : i + 1] * b)
    lo = cols[..., :NLIMBS]
    lo = lo.at[..., : NLIMBS - 1].add(38 * cols[..., NLIMBS:])
    return carry(lo, passes=4)


def _mul_conv(a, b):
    """Schoolbook + 38-fold as ONE depthwise int32 convolution.

    Polynomial multiplication is a convolution; on TPU, XLA's conv emitter
    runs it ~1.8x faster than the 32-step shifted-accumulate loop and
    compiles ~10x faster (one HLO op instead of 32 dynamic-update-slices).
    The mod-p fold is folded INTO the kernel: correlating b against
    c = [38*a[1:] ‖ a] yields directly
        out[n] = sum_{i+j=n} a_i b_j + 38 * sum_{i+j=n+32} a_i b_j
    i.e. the reduced 32 columns (2^256 = 38 mod p), skipping the separate
    fold pass. Bounds unchanged: |col| < 39 * 32 * 2^18 < 2^28.3.
    """
    from jax import lax

    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape)
    b = jnp.broadcast_to(b, shape)
    lead = shape[:-1]
    g = 1
    for d in lead:
        g *= int(d)
    af = a.reshape(g, NLIMBS)
    bf = b.reshape(g, NLIMBS)
    kern = jnp.concatenate([38 * af[:, 1:], af], axis=-1)  # (g, 63)
    cols = lax.conv_general_dilated(
        bf[None],  # (1, g, 32)   NCW
        kern[:, None, ::-1],  # (g, 1, 63)   OIW, reversed -> true convolution
        window_strides=(1,),
        padding=[(NLIMBS - 1, NLIMBS - 1)],
        feature_group_count=g,
        dimension_numbers=("NCW", "OIW", "NCW"),
    )[0]  # (g, 32)
    return carry(cols, passes=4).reshape(shape)


def _pick_mul():
    import os

    import jax

    # conv wins on TPU-class backends; the shifted-accumulate loop wins on
    # XLA:CPU (measured ~2x each way). A backend that fails to initialize
    # raises here: answering "cpu" would hide a dead chip. On a TPU nothing
    # else is consulted; off it PBFT_FIELD_MUL may name either lowering (the
    # CPU dry run asks for conv, which compiles faster there).
    backend = jax.default_backend()
    impl = "auto" if backend == "tpu" else os.environ.get("PBFT_FIELD_MUL", "auto")
    if impl == "conv":
        return _mul_conv
    if impl == "schoolbook":
        return _mul_schoolbook
    return _mul_schoolbook if backend == "cpu" else _mul_conv


def mul(a, b):
    """Field multiply. Inputs: carried limbs |x| < 2^9. Output: carried.

    Columns |col| < 32 * 2^18 = 2^23; the 38-fold keeps the reduced
    columns < 39 * 2^23 < 2^28.3 — inside int32 with margin. Two
    implementations (picked per backend; off the TPU PBFT_FIELD_MUL overrides)."""
    global _MUL_IMPL
    if _MUL_IMPL is None:
        _MUL_IMPL = _pick_mul()
    return _MUL_IMPL(a, b)


_MUL_IMPL = None


def sqr(a):
    return mul(a, a)


def mul_small(a, k: int):
    """Multiply by a small static scalar (k*limb must stay inside int32)."""
    return carry(a * k, passes=4)


def _sqr_body(_, v):
    return sqr(v)


def pow2k(x, k: int):
    """x^(2^k) by k squarings (static k; fori_loop body is a module-level
    function so jax's trace cache hits across calls)."""
    from jax import lax

    if k <= 4:
        for _ in range(k):
            x = sqr(x)
        return x
    return lax.fori_loop(0, k, _sqr_body, x)


def _inv_chain(z, mul=None, sqr=None, pow2k=None):
    """Shared ladder: returns (z^(2^250-1), z^11).

    The classic curve25519 exponent chain; pieces are reused by both inv()
    (exponent p-2 = 2^255-21) and pow_p58() (exponent (p-5)/8 = 2^252-3).
    The ops are parameters so pallas_kernels runs the IDENTICAL chain with
    its in-kernel primitives — one definition, two backends (divergence
    between verifier backends would split replicas)."""
    mul = mul or globals()["mul"]
    sqr = sqr or globals()["sqr"]
    pow2k = pow2k or globals()["pow2k"]
    z2 = sqr(z)
    z8 = pow2k(z2, 2)
    z9 = mul(z, z8)
    z11 = mul(z2, z9)
    z22 = sqr(z11)
    z_5_0 = mul(z9, z22)  # 2^5 - 1
    z_10_0 = mul(pow2k(z_5_0, 5), z_5_0)  # 2^10 - 1
    z_20_0 = mul(pow2k(z_10_0, 10), z_10_0)  # 2^20 - 1
    z_40_0 = mul(pow2k(z_20_0, 20), z_20_0)  # 2^40 - 1
    z_50_0 = mul(pow2k(z_40_0, 10), z_10_0)  # 2^50 - 1
    z_100_0 = mul(pow2k(z_50_0, 50), z_50_0)  # 2^100 - 1
    z_200_0 = mul(pow2k(z_100_0, 100), z_100_0)  # 2^200 - 1
    z_250_0 = mul(pow2k(z_200_0, 50), z_50_0)  # 2^250 - 1
    return z_250_0, z11


def inv(z):
    """z^(p-2) = z^(2^255-21): the field inverse (inv(0) = 0)."""
    z_250_0, z11 = _inv_chain(z)
    return mul(pow2k(z_250_0, 5), z11)


def pow_p58(z):
    """z^((p-5)/8) = z^(2^252-3), used for the square-root-ratio."""
    z_250_0, _ = _inv_chain(z)
    return mul(pow2k(z_250_0, 2), z)


@jax.jit
def canon(x):
    """Canonical form: limbs in [0, 2^8), value in [0, p). Jitted so that a
    program that canonicalizes seven times (a verification does) traces and
    lowers these ~800 limb operations once, as a function it calls: what
    XLA compiles is the same, what warm-up pays for each shape is not."""
    x = carry_seq(carry_seq(x))
    # Force non-negativity: add 2p (== 0 mod p); the value may have been a
    # small negative after signed folds.
    x = carry_seq(x + jnp.asarray(_2P_LIMBS))
    # Fold bit 255+: value < 2^256 -> < 2^255 + 38.
    hi = x[..., NLIMBS - 1] >> (RADIX - 1)
    x = x.at[..., NLIMBS - 1].add(-(hi << (RADIX - 1)))
    x = x.at[..., 0].add(19 * hi)
    x = carry_seq(x)
    # At most two conditional subtracts of p remain.
    for _ in range(2):
        b = jnp.zeros_like(x[..., 0])
        digits = []
        for i in range(NLIMBS):
            v = x[..., i] - jnp.asarray(_P_LIMBS)[i] + b
            digits.append(v & MASK)
            b = v >> RADIX
        y = jnp.stack(digits, axis=-1)
        ge = b == 0  # no final borrow -> x >= p
        x = jnp.where(ge[..., None], y, x)
    return x


def eq(a, b):
    return jnp.all(canon(a) == canon(b), axis=-1)


def is_zero(a):
    return jnp.all(canon(a) == 0, axis=-1)


def bytes_to_limbs(b):
    """(…,n) uint8 little-endian -> (…,n) int32 limbs. At radix 2^8 the
    byte string IS the limb vector (32 bytes -> 32 limbs, 64-byte digests
    -> 64 limbs); only the dtype changes."""
    return jnp.asarray(b).astype(_DTYPE)


def limbs_to_bytes(x):
    """Canonical limbs -> (…,32) uint8 little-endian."""
    return canon(x).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Scalar arithmetic mod L (group order), for the challenge hash and S check.
# ---------------------------------------------------------------------------

_L_LIMBS = limbs_const(L)

# 512-bit inputs are 64 limbs; all fold intermediates live in 65 limbs.
_NL512 = 65


def _plain_carry(x, n: int):
    """Carry pass without any modular fold (plain multi-precision integer)."""
    out = []
    c = jnp.zeros_like(x[..., 0])
    for i in range(n):
        v = x[..., i] + c
        c = v >> RADIX
        out.append(v & MASK)
    out[-1] = out[-1] + (c << RADIX)  # keep any residue in the top limb
    return jnp.stack(out, axis=-1)


def _mul_by_const(x, nx: int, const_limbs: np.ndarray, nout: int):
    """Multi-precision multiply of x (nx limbs) by a static constant."""
    k = len(const_limbs)
    cols = jnp.zeros(x.shape[:-1] + (nout,), _DTYPE)
    for i in range(k):
        ci = int(const_limbs[i])
        if ci == 0:
            continue
        hi = min(nx, nout - i)
        cols = cols.at[..., i : i + hi].add(ci * x[..., :hi])
    return cols


_FOLD_M: list[np.ndarray] = []


def _build_fold_constants():
    """Static M_k*L addends keeping each 2^252-fold non-negative.

    After normalizing to S_k bits, hi < 2^(S_k-252) so hi*delta <
    2^(S_k-252)*2^125. Pick M_k = ceil(2^(S_k-127)/L)+1; then
    lo - hi*delta + M_k*L is in [0, 2^252 + (M_k+1)*L)."""
    sizes = [512, 390, 266]
    for s in sizes:
        m = (1 << max(s - 127, 0)) // L + 2
        _FOLD_M.append(limbs_const(m * L, _NL512))


_build_fold_constants()
_DELTA_LIMBS = limbs_const(DELTA, 16)


def reduce512_mod_l(x):
    """(…,64) limbs (512-bit LE integer) -> (…,32) limbs in [0, L)."""
    x = jnp.concatenate(
        [jnp.asarray(x, _DTYPE), jnp.zeros(x.shape[:-1] + (1,), _DTYPE)],
        axis=-1,
    )
    x = _plain_carry(x, _NL512)
    for m_l in _FOLD_M:
        # hi = x >> 252: bit 252 sits at limb 31 bit 4, so each hi limb
        # stitches the top nibble of x[31+i] to the low nibble of x[32+i].
        hi = (x[..., 31:64] >> 4) | ((x[..., 32:65] & 0xF) << 4)
        hi = jnp.concatenate([hi, x[..., 64:65] >> 4], axis=-1)  # 34 limbs
        lo = x.at[..., 31].set(x[..., 31] & 0xF)
        lo = lo.at[..., 32:].set(0)
        prod = _mul_by_const(hi, 34, _DELTA_LIMBS, 50)
        prod = jnp.concatenate(
            [prod, jnp.zeros(prod.shape[:-1] + (_NL512 - 50,), _DTYPE)],
            axis=-1,
        )
        x = lo - prod + jnp.asarray(m_l)
        x = _plain_carry(x, _NL512)
    # Value now < 2^254-ish: at most 3 conditional subtracts of L.
    x = x[..., : NLIMBS + 1]
    l_ext = np.concatenate([_L_LIMBS, np.zeros(1, np.int32)])
    for _ in range(4):
        b = jnp.zeros_like(x[..., 0])
        digits = []
        for i in range(NLIMBS + 1):
            v = x[..., i] - jnp.asarray(l_ext)[i] + b
            digits.append(v & MASK)
            b = v >> RADIX
        y = jnp.stack(digits, axis=-1)
        x = jnp.where((b == 0)[..., None], y, x)
    return x[..., :NLIMBS]


def scalar_lt_l(s):
    """(…,32) limbs -> bool: is the 256-bit scalar strictly below L?"""
    b = jnp.zeros_like(s[..., 0])
    for i in range(NLIMBS):
        v = s[..., i] - jnp.asarray(_L_LIMBS)[i] + b
        b = v >> RADIX
    return b < 0


def scalar_bits(s, nbits: int = 256):
    """(…,32) limbs -> (…, nbits) int32 bit array, LSB first."""
    shifts = jnp.arange(RADIX, dtype=_DTYPE)
    bits = (s[..., :, None] >> shifts) & 1
    return bits.reshape(s.shape[:-1] + (NLIMBS * RADIX,))[..., :nbits].astype(
        jnp.int32
    )
