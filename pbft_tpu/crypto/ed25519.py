"""Ed25519 verification in JAX, built for one-XLA-launch batch verification.

The consensus hot path (SURVEY.md §3.4-3.5): every PREPARE/COMMIT quorum needs
2f / 2f+1 signatures verified. The reference left signature checks as TODOs
(reference src/behavior.rs:127, :185); here they are the centerpiece, designed
so a whole view-round's quorum certificates verify as one `jax.vmap` batch.

Scalar pipeline per item (pub 32B, msg 32B digest, sig 64B = R||S):
  1. h = SHA-512(R || pub || msg) reduced mod L      (sha512.py + field.py)
  2. decompress pub -> A (reject non-canonical y, off-curve, x=0&sign)
  3. P = [S]B + [h](-A) via a 256-step Shamir (joint double-scalar) ladder
     over the 4-entry table {O, B, -A, B-A}, using complete extended
     twisted-Edwards addition (a=-1, add-2008-hwcd-3) -- completeness means
     no data-dependent branches, which is exactly what XLA wants.
  4. valid = canonical(S) & ok(A) & (compress(P) == R)
     (comparing compressed bytes rejects non-canonical R for free).

Cofactorless equation, strict S < L: bit-for-bit the same accept set as the
pure-Python oracle pbft_tpu.crypto.ref (RFC 8032).

Points are tuples (X, Y, Z, T) of (..., 32)-limb int32 field elements with
T = XY/Z (radix 2^8 — native width for the TPU's 32-bit vector unit; see
field.py). All control flow is static; everything vmaps/jits.
"""

from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from . import field as F
from . import ref
from .sha512 import sha512

# Static curve constants, as limb arrays (computed from the oracle's big
# ints; ref.py is the RFC 8032 ground truth).
_D = F.limbs_const(ref.D)
_D2 = F.limbs_const(2 * ref.D % F.P)
_SQRT_M1 = F.limbs_const(pow(2, (F.P - 1) // 4, F.P))
_BX = F.limbs_const(ref.BASE[0])
_BY = F.limbs_const(ref.BASE[1])
_BT = F.limbs_const(ref.BASE[0] * ref.BASE[1] % F.P)
_ONE = F.limbs_const(1)
_ZERO = F.limbs_const(0)

# [0]B, [1]B, [2]B, [3]B in extended coords (X, Y, Z=1, T=XY) — the static
# row of the Shamir table (ref.shamir_row0, shared with pallas_kernels),
# precomputed so the ladder never spends traced point ops on base multiples.
_ROW0 = tuple(
    np.stack([F.limbs_const(v) for v in coords])
    for coords in zip(*ref.shamir_row0())
)  # 4 arrays of shape (4, 32): X-row, Y-row, Z-row, T-row


def identity(shape=()):
    z = jnp.broadcast_to(jnp.asarray(_ZERO), shape + (F.NLIMBS,))
    o = jnp.broadcast_to(jnp.asarray(_ONE), shape + (F.NLIMBS,))
    return (z, o, o, z)


def base_point(shape=()):
    return tuple(
        jnp.broadcast_to(jnp.asarray(c), shape + (F.NLIMBS,))
        for c in (_BX, _BY, _ONE, _BT)
    )


def point_add(p, q):
    """Complete unified addition (a=-1 twisted Edwards, extended coords)."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = F.mul(F.sub(y1, x1), F.sub(y2, x2))
    b = F.mul(F.add(y1, x1), F.add(y2, x2))
    c = F.mul(F.mul(t1, jnp.asarray(_D2)), t2)
    d = F.mul_small(F.mul(z1, z2), 2)
    e = F.sub(b, a)
    f = F.sub(d, c)
    g = F.add(d, c)
    h = F.add(b, a)
    return (F.mul(e, f), F.mul(g, h), F.mul(f, g), F.mul(e, h))


def point_double(p):
    """Dedicated doubling (dbl-2008-hwcd, a=-1): 4M+4S, vs 9M for the
    unified add — the ladder is doubling-dominated, so this matters."""
    x1, y1, z1, _ = p
    a = F.sqr(x1)
    b = F.sqr(y1)
    c = F.mul_small(F.sqr(z1), 2)
    d = F.neg(a)  # a = -1 twist
    e = F.sub(F.sub(F.sqr(F.add(x1, y1)), a), b)
    g = F.add(d, b)
    f = F.sub(g, c)
    h = F.sub(d, b)
    return (F.mul(e, f), F.mul(g, h), F.mul(f, g), F.mul(e, h))


def point_neg(p):
    x, y, z, t = p
    return (F.neg(x), y, z, F.neg(t))


# The rows a chip under which a compiled shape keeps the XLA chains. A tile
# of pallas_kernels is 1,024 items (a limb a whole vector register) and costs
# the same part full as full, so the line is where a part-full tile beats
# the XLA chains: at 256 rows a chip it does (3.61 ms on the device for the
# 256-slot program against 5.318, one v5e, PR 43); at 16 and 64 rows nothing
# is served (the serving table sends those windows to the 256-slot program).
VMEM_CHAIN_ROWS = 256


def chains_for(rows: int, backend: str | None = None) -> str:
    """``"vmem"`` or ``"xla"``: how a program compiled for ``rows`` rows a
    chip runs its three long multiply chains (pow_p58, inv, the Shamir
    ladder). On a TPU backend a shape of ``VMEM_CHAIN_ROWS`` rows or more
    runs them out of VMEM (pallas_kernels.py, in tiles of 1,024 padded with
    zeros); a smaller shape, and every other backend,
    runs the XLA chains. One algorithm, two lowerings, chosen by what the
    code can see: the backend and the static row count, as field._pick_mul
    chooses by the backend. No environment variable, flag or key enters.

    Measured and sized for ONE chip, the v5e: the row line (3.61 ms against
    5.318), the 9.9 MiB a tile holds and pallas_kernels' 19.8 MiB
    ``vmem_limit_bytes`` are that chip's. Any backend named ``tpu`` gets
    ``"vmem"``; a generation that gives a kernel less VMEM fails at Mosaic's
    compile in warm-up (``verifyd --backend jax`` then exits non-zero) and
    does not fall back to XLA. What a shape DID take is
    ``parallel.chains_of`` of its executable, not this rule."""
    backend = backend or jax.default_backend()
    return "vmem" if backend == "tpu" and rows >= VMEM_CHAIN_ROWS else "xla"


def _use_pallas(x) -> bool:
    """Whether the program being traced for ``x`` (…, k) takes the VMEM
    chains: :func:`chains_for` of its rows."""
    return chains_for(math.prod(x.shape[:-1])) == "vmem"


def _impl_pow_p58(z):
    if _use_pallas(z):
        from . import pallas_kernels

        return pallas_kernels.pow_p58(z)
    return F.pow_p58(z)


def _impl_inv(z):
    if _use_pallas(z):
        from . import pallas_kernels

        return pallas_kernels.inv(z)
    return F.inv(z)


def sqrt_ratio(u, v):
    """(ok, r) with v*r^2 == u when ok; the p = 5 (mod 8) method."""
    v2 = F.sqr(v)
    v3 = F.mul(v, v2)
    v7 = F.mul(v3, F.sqr(v2))
    r = F.mul(F.mul(u, v3), _impl_pow_p58(F.mul(u, v7)))
    check = F.mul(v, F.sqr(r))
    ok_plus = F.eq(check, u)
    ok_minus = F.eq(check, F.neg(u))
    r = jnp.where(ok_minus[..., None], F.mul(r, jnp.asarray(_SQRT_M1)), r)
    return ok_plus | ok_minus, r


def decompress(ybytes):
    """(…,32) uint8 -> (ok, point). RFC 8032 §5.1.3 decoding."""
    ybytes = jnp.asarray(ybytes, jnp.uint8)
    sign = (ybytes[..., 31] >> 7).astype(jnp.int32)
    masked = ybytes.at[..., 31].set(ybytes[..., 31] & 0x7F)
    y = F.bytes_to_limbs(masked)
    # Canonical check: y < p.
    b = jnp.zeros_like(y[..., 0])
    for i in range(F.NLIMBS):
        b = (y[..., i] - jnp.asarray(F._P_LIMBS)[i] + b) >> F.RADIX
    ok_canon = b < 0
    y2 = F.sqr(y)
    u = F.sub(y2, jnp.asarray(_ONE))
    v = F.add(F.mul(y2, jnp.asarray(_D)), jnp.asarray(_ONE))
    ok_sqrt, x = sqrt_ratio(u, v)
    x = F.canon(x)
    x_zero = jnp.all(x == 0, axis=-1)
    ok = ok_canon & ok_sqrt & ~(x_zero & (sign == 1))
    flip = (x[..., 0] & 1) != sign
    x = jnp.where(flip[..., None], F.neg(x), x)
    one = jnp.broadcast_to(jnp.asarray(_ONE), y.shape)
    return ok, (x, y, one, F.mul(x, y))


def compress(p):
    """Point -> (…,32) uint8 canonical encoding."""
    x, y, z, _ = p
    zi = _impl_inv(z)
    xa = F.canon(F.mul(x, zi))
    ybytes = F.limbs_to_bytes(F.mul(y, zi))
    sign = (xa[..., 0] & 1).astype(jnp.uint8)
    return ybytes.at[..., 31].add(sign << 7)


def shamir_ladder(s_bits, h_bits, a_neg):
    """[S]B + [h]*(-A) with a joint 2-bit window: one 16-entry table lookup
    per pair of scalar bits. 128 iterations of (2 doublings + 1 addition)
    instead of 256 x (double + add) — ~40% fewer point operations, and the
    whole loop is static control flow (fori_loop) with select-based table
    lookup, exactly what XLA tiles well.

    s_bits, h_bits: (…,256) int32 LSB-first; a_neg: point with (…,32) coords.
    """
    shape = s_bits.shape[:-1]
    # Table E[s + 4h] = [s]B + [h](-A) for s, h in 0..3, held as STACKED
    # arrays (16, …, 32) per coordinate. The B-multiples row is a static
    # constant (_ROW0); the three -A rows cost one doubling, one addition,
    # and ONE batched addition traced over a (3, 4) leading axis — the
    # stacked layout keeps the traced graph a single point_add instead of
    # twelve, and the mux below is 4 selects per coordinate instead of 15.
    row0 = tuple(
        jnp.broadcast_to(
            jnp.asarray(c).reshape((4,) + (1,) * len(shape) + (F.NLIMBS,)),
            (4,) + shape + (F.NLIMBS,),
        )
        for c in _ROW0
    )
    a1 = a_neg
    a2 = point_double(a1)
    a3 = point_add(a2, a1)
    arows = tuple(
        jnp.stack([a1[c], a2[c], a3[c]], axis=0)[:, None]
        for c in range(4)
    )  # (3, 1, …, 32) per coordinate
    prods = point_add(tuple(r[None] for r in row0), arows)  # (3, 4, …, 32)
    entries = tuple(
        jnp.concatenate([row0[c][None], prods[c]], axis=0).reshape(
            (16,) + shape + (F.NLIMBS,)
        )
        for c in range(4)
    )  # index = 4h + s

    def mux(bits, table):
        """table: coordinate arrays with a leading 2^len(bits) axis;
        bits LSB-first halve it with one select per level."""
        cur = table
        for b in bits:
            cond = (b == 1)[..., None]
            cur = tuple(jnp.where(cond, c[1::2], c[0::2]) for c in cur)
        return tuple(c[0] for c in cur)

    def body(k, acc):
        step = 127 - k
        s0 = lax.dynamic_index_in_dim(s_bits, 2 * step, axis=-1, keepdims=False)
        s1 = lax.dynamic_index_in_dim(s_bits, 2 * step + 1, axis=-1, keepdims=False)
        h0 = lax.dynamic_index_in_dim(h_bits, 2 * step, axis=-1, keepdims=False)
        h1 = lax.dynamic_index_in_dim(h_bits, 2 * step + 1, axis=-1, keepdims=False)
        sel = mux([s0, s1, h0, h1], entries)
        acc = point_double(point_double(acc))
        return point_add(acc, sel)

    return lax.fori_loop(0, 128, body, identity(shape))


def verify_kernel(pub, msg, sig):
    """(…,32),(…,32),(…,64) uint8 -> (…,) bool. Batch-agnostic."""
    pub = jnp.asarray(pub, jnp.uint8)
    msg = jnp.asarray(msg, jnp.uint8)
    sig = jnp.asarray(sig, jnp.uint8)
    r_bytes = sig[..., :32]
    s_bytes = sig[..., 32:]
    # The four stages carry names (jax.named_scope -> the operations'
    # op_name), so that a profiler trace can say which one a launch's
    # device time went to; names only, the computation is the same.
    with jax.named_scope("sha512_challenge"):
        # Challenge hash: h = SHA512(R || A || M) mod L.
        h_raw = sha512(jnp.concatenate([r_bytes, pub, msg], axis=-1))
        h = F.reduce512_mod_l(F.bytes_to_limbs(h_raw))
        s = F.bytes_to_limbs(s_bytes)
        s_ok = F.scalar_lt_l(s)
    with jax.named_scope("decompress"):
        ok_a, a_pt = decompress(pub)
    with jax.named_scope("ladder"):
        if _use_pallas(pub):
            from . import pallas_kernels

            p = pallas_kernels.ladder(
                F.scalar_bits(s), F.scalar_bits(h), point_neg(a_pt)
            )
        else:
            p = shamir_ladder(F.scalar_bits(s), F.scalar_bits(h), point_neg(a_pt))
    with jax.named_scope("compress"):
        enc = compress(p)
        match = jnp.all(enc == r_bytes, axis=-1)
    return ok_a & s_ok & match
