"""Pallas TPU kernels for the Ed25519 hot path.

Why these exist: the XLA pipeline in field.py/ed25519.py expresses every
field multiply as its own HLO op (a depthwise conv + carry chain). XLA
fuses the elementwise carries, but the convs break fusion, so the ~2,200
sequential multiplies of one verification each round-trip their (B, 32)
operands through HBM. These kernels hold whole multiply *chains* in VMEM:

- ``inv`` / ``pow_p58`` — the ~254-squaring exponent ladders of
  compress/decompress as ONE kernel launch each;
- ``ladder`` — the full 128-iteration Shamir double-scalar ladder
  (2 doublings + 1 table addition per step, the dominant ~85% of a
  verify) as one kernel, with the 16-entry point table VMEM-resident.

Layout: kernels are **limb-major** — a field element batch is a (32, TB)
int32 tile (limbs on sublanes, batch on lanes), so every carry/fold is a
sublane rotate of a fully-populated 128-lane vector. The public wrappers
transpose at the boundary (one (B,32)->(32,B) transpose per kernel call,
amortized over hundreds of fused multiplies).

The arithmetic (radix-2^8 signed limbs, 38-fold at 2^256, 2/4-pass
vectorized carries) is bit-identical to field.py — same bounds proof, same
results; tests/test_pallas_kernels.py pins equivalence against both
field.py and the RFC 8032 oracle. ``PBFT_PALLAS=1`` switches
ed25519.verify_kernel onto these kernels (compiled Mosaic on the TPU;
anywhere else only under ``PBFT_PALLAS_INTERPRET=1``, the interpreter).

Reference analogue: none — the reference left signature verification as
TODOs (src/behavior.rs:127, :185); this is the TPU-native centerpiece the
rebuild adds (SURVEY.md §5, §7).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref
from .field import NLIMBS, RADIX, MASK, P, limbs_const

# Lane-tile width. 128 lanes is the VPU width; the ladder kernel's point
# table is 16 entries x 4 coords x (32, TB) int32 = TB/128 MB, so TB=128
# keeps the whole working set ~2 MB of the ~16 MB VMEM. Overridable for
# interpret-mode tests (narrow tiles make the emulated kernel tractable).
import os as _os

TB = int(_os.environ.get("PBFT_PALLAS_TB", "128"))

_DTYPE = jnp.int32

# Static constants, shaped (32, 1) for limb-major broadcast.
def _cl(v: int) -> np.ndarray:
    return limbs_const(v).reshape(NLIMBS, 1)


_C_2P = _cl(2 * P)
_C_D2 = _cl(2 * ref.D % P)
# [s]B rows of the Shamir table: identity, B, 2B, 3B in extended coords
# (ref.shamir_row0 — the same source ed25519._ROW0 is built from).
_ROW0 = [tuple(_cl(v) for v in coords) for coords in ref.shamir_row0()]


# ---------------------------------------------------------------------------
# In-kernel field arithmetic on limb-major (32, TB) values.
# ---------------------------------------------------------------------------


def _iota():
    return lax.broadcasted_iota(_DTYPE, (NLIMBS, 1), 0)


def _carry(x, passes: int):
    """Vectorized carry, limb-major: the carry leaving each sublane moves
    down one sublane (roll by 1); the one leaving sublane 31 re-enters
    sublane 0 as *38 (2^256 = 38 mod p). Same convergence bounds as
    field.carry."""
    w0 = jnp.where(_iota() == 0, 38, 1)
    for _ in range(passes):
        lo = x & MASK
        hi = x >> RADIX  # arithmetic shift: exact floor for negatives
        x = lo + w0 * pltpu.roll(hi, 1, 0)
    return x


def _mm(a, b):
    """Field multiply with the 38-fold woven into the accumulation:
    out[n] = sum_i a_i * b_[(n-i) mod 32] * (38 if n < i else 1).
    Inputs carried (|limb| < 2^10.3), output carried; bounds identical to
    field._mul_schoolbook (cols < 2^28.3, inside int32)."""
    io = _iota()
    acc = a[0:1, :] * b
    for i in range(1, NLIMBS):
        w = jnp.where(io < i, 38, 1)
        # pltpu.roll is the sublane rotate; jnp.roll lowers to slices +
        # concatenate, which Mosaic refuses (a zero-size slice at shift 0).
        acc = acc + w * (a[i : i + 1, :] * pltpu.roll(b, i, 0))
    return _carry(acc, 4)


def _sq(a):
    return _mm(a, a)


def _madd(a, b):
    return _carry(a + b, 2)


def _msub(a, b):
    return _carry(a - b, 2)


def _mneg(a, c2p):
    return _carry(c2p - a, 2)


def _mul_small(a, k: int):
    return _carry(a * k, 4)


def _pow2k(x, k: int):
    if k <= 4:
        for _ in range(k):
            x = _sq(x)
        return x
    return lax.fori_loop(0, k, lambda _, v: _sq(v), x)


def _inv_chain(z):
    """(z^(2^250-1), z^11): field._inv_chain run with the in-kernel ops —
    one chain definition shared across verifier backends."""
    from .field import _inv_chain as chain

    return chain(z, mul=_mm, sqr=_sq, pow2k=_pow2k)


# ---------------------------------------------------------------------------
# In-kernel point arithmetic (a=-1 twisted Edwards, extended coords).
# ---------------------------------------------------------------------------


def _padd(p, q, cd2):
    """add-2008-hwcd-3 — mirrors ed25519.point_add. cd2 = 2d limbs."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = _mm(_msub(y1, x1), _msub(y2, x2))
    b = _mm(_madd(y1, x1), _madd(y2, x2))
    c = _mm(_mm(t1, cd2), t2)
    d = _mul_small(_mm(z1, z2), 2)
    e = _msub(b, a)
    f = _msub(d, c)
    g = _madd(d, c)
    h = _madd(b, a)
    return (_mm(e, f), _mm(g, h), _mm(f, g), _mm(e, h))


def _pdbl(p, c2p):
    """dbl-2008-hwcd — mirrors ed25519.point_double. c2p = 2p limbs."""
    x1, y1, z1, _ = p
    a = _sq(x1)
    b = _sq(y1)
    c = _mul_small(_sq(z1), 2)
    d = _mneg(a, c2p)
    e = _msub(_msub(_sq(_madd(x1, y1)), a), b)
    g = _madd(d, b)
    f = _msub(g, c)
    h = _msub(d, b)
    return (_mm(e, f), _mm(g, h), _mm(f, g), _mm(e, h))


# ---------------------------------------------------------------------------
# Kernel bodies.
# ---------------------------------------------------------------------------


def _inv_kernel(z_ref, out_ref):
    z = z_ref[:]
    z_250_0, z11 = _inv_chain(z)
    out_ref[:] = _mm(_pow2k(z_250_0, 5), z11)


def _p58_kernel(z_ref, out_ref):
    z = z_ref[:]
    z_250_0, _ = _inv_chain(z)
    out_ref[:] = _mm(_pow2k(z_250_0, 2), z)


# Constant matrix for the ladder kernel, limb-major (32, K): pallas
# kernels may not close over array constants, so every static limb vector
# rides in as one input block. Columns: 0 = 2p, 1 = 2d, 2 = 1, then
# 3 + 4*s + c = coordinate c of [s]B (the h=0 table row).
_NCONST = 3 + 16
_LADDER_CONSTS = np.zeros((NLIMBS, 32), np.int32)  # lane-padded to 32
_LADDER_CONSTS[:, 0:1] = _C_2P
_LADDER_CONSTS[:, 1:2] = _C_D2
_LADDER_CONSTS[:, 2:3] = _cl(1)
for _s, _entry in enumerate(_ROW0):
    for _c, _limbs in enumerate(_entry):
        _LADDER_CONSTS[:, 3 + 4 * _s + _c : 4 + 4 * _s + _c] = _limbs


def _ladder_kernel(consts_ref, digits_ref, ax_ref, ay_ref, az_ref, at_ref, *out_refs):
    """The full Shamir ladder: acc = sum over 128 steps of 4*acc + E[d_k],
    where E[s + 4h] = [s]B + [h](-A) and d_k is the k-th (MSB-first) pair
    of (S, h) bit-digits, precomputed host-side as one int in 0..15.

    The 16-entry table lives in VMEM for the whole kernel; each step is 2
    doublings + 1 unified addition + a 4-level halving mux — identical
    math to ed25519.shamir_ladder."""
    c2p = consts_ref[:, 0:1]
    cd2 = consts_ref[:, 1:2]
    cone = consts_ref[:, 2:3]
    a1 = (ax_ref[:], ay_ref[:], az_ref[:], at_ref[:])
    a2 = _pdbl(a1, c2p)
    a3 = _padd(a2, a1, cd2)
    shape = a1[0].shape
    tb = shape[-1]
    row0 = [
        tuple(
            jnp.broadcast_to(consts_ref[:, 3 + 4 * s + c : 4 + 4 * s + c], shape)
            for c in range(4)
        )
        for s in range(4)
    ]
    # The 12 data-dependent table entries E[4h + s] = [s]B + [h](-A)
    # (h = 1..3) as ONE lane-stacked addition: [s]B rows tiled 3x against
    # [h](-A) repeated 4x — a single _padd on (32, 12*TB) instead of 12
    # unrolled point additions (12x smaller kernel graph, same math).
    r_stack = tuple(
        jnp.concatenate([row0[s][c] for _ in range(3) for s in range(4)], axis=1)
        for c in range(4)
    )
    a_stack = tuple(
        jnp.concatenate(
            [ah[c] for ah in (a1, a2, a3) for _ in range(4)], axis=1
        )
        for c in range(4)
    )
    prods = _padd(r_stack, a_stack, cd2)
    entries = list(row0) + [
        tuple(prods[c][:, j * tb : (j + 1) * tb] for c in range(4))
        for j in range(12)
    ]

    zero = jnp.zeros(shape, _DTYPE)
    one = jnp.broadcast_to(cone, shape)
    ident = (zero, one, one, zero)

    def mux(d):
        cur = entries
        for level in range(4):
            bit = (d >> level) & 1
            cond = bit == 1  # (1, TB)
            cur = [
                tuple(
                    jnp.where(cond, hi_c, lo_c)
                    for lo_c, hi_c in zip(lo, hi)
                )
                for lo, hi in zip(cur[0::2], cur[1::2])
            ]
        return cur[0]

    def body(k, acc):
        d = digits_ref[pl.ds(k, 1), :]  # (1, TB), values 0..15
        acc = _pdbl(_pdbl(acc, c2p), c2p)
        return _padd(acc, mux(d), cd2)

    acc = lax.fori_loop(0, 128, body, ident)
    for o, c in zip(out_refs, acc):
        o[:] = c


# ---------------------------------------------------------------------------
# Host-side wrappers: batch-major (..., 32) <-> limb-major (32, B) plus
# lane padding, one pallas_call per chain.
# ---------------------------------------------------------------------------


def _use_interpret() -> bool:
    """Interpret mode runs only when asked for by name; otherwise the
    kernels compile (Mosaic), which ed25519._use_pallas has already
    confined to the TPU backend."""
    return _os.environ.get("PBFT_PALLAS_INTERPRET") == "1"


def _to_lm(x, b_pad: int):
    """(g, 32) -> (32, b_pad) limb-major with lane padding."""
    g = x.shape[0]
    xt = jnp.swapaxes(x, -1, -2)
    if g < b_pad:
        xt = jnp.pad(xt, ((0, 0), (0, b_pad - g)))
    return xt


def _block(n_rows: int):
    return pl.BlockSpec((n_rows, TB), lambda i: (0, i), memory_space=pltpu.VMEM)


@functools.partial(jax.jit, static_argnames=("kernel_name",))
def _run_chain(x, kernel_name: str):
    """Shared driver for the single-input chain kernels (inv, p58)."""
    kernel = {"inv": _inv_kernel, "p58": _p58_kernel}[kernel_name]
    shape = x.shape
    g = 1
    for d in shape[:-1]:
        g *= int(d)
    xf = x.reshape(g, NLIMBS)
    b_pad = max(TB, ((g + TB - 1) // TB) * TB)
    xlm = _to_lm(xf, b_pad)
    out = pl.pallas_call(
        kernel,
        grid=(b_pad // TB,),
        in_specs=[_block(NLIMBS)],
        out_specs=_block(NLIMBS),
        out_shape=jax.ShapeDtypeStruct((NLIMBS, b_pad), _DTYPE),
        interpret=_use_interpret(),
    )(xlm)
    return jnp.swapaxes(out, -1, -2)[:g].reshape(shape)


def inv(z):
    """Drop-in for field.inv (z^(p-2), inv(0) = 0) as one fused kernel."""
    return _run_chain(z, kernel_name="inv")


def pow_p58(z):
    """Drop-in for field.pow_p58 (z^((p-5)/8)) as one fused kernel."""
    return _run_chain(z, kernel_name="p58")


@jax.jit
def ladder(s_bits, h_bits, a_neg):
    """Drop-in for ed25519.shamir_ladder: [S]B + [h](-A).

    s_bits, h_bits: (..., 256) int32 LSB-first; a_neg: point tuple with
    (..., 32) coords. Returns the accumulator point, batch-major."""
    shape = s_bits.shape[:-1]
    g = 1
    for d in shape:
        g *= int(d)
    b_pad = max(TB, ((g + TB - 1) // TB) * TB)

    # Digit schedule, MSB-first: step k consumes bit-pair 127-k of each
    # scalar -> d = s0 + 2 s1 + 4 h0 + 8 h1 in 0..15, laid out (128, B).
    sb = s_bits.reshape(g, 256)
    hb = h_bits.reshape(g, 256)
    dig = (
        sb[:, 0::2] + 2 * sb[:, 1::2] + 4 * hb[:, 0::2] + 8 * hb[:, 1::2]
    )  # (g, 128), LSB-first pairs
    dig = dig[:, ::-1]  # MSB-first
    dig_lm = _to_lm(dig, b_pad)  # (128, b_pad)

    coords = [
        _to_lm(c.reshape(g, NLIMBS), b_pad) for c in a_neg
    ]  # 4 x (32, b_pad)

    const_spec = pl.BlockSpec(
        (NLIMBS, 32), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    outs = pl.pallas_call(
        _ladder_kernel,
        grid=(b_pad // TB,),
        in_specs=[const_spec, _block(128)] + [_block(NLIMBS)] * 4,
        out_specs=[_block(NLIMBS)] * 4,
        out_shape=[jax.ShapeDtypeStruct((NLIMBS, b_pad), _DTYPE)] * 4,
        interpret=_use_interpret(),
    )(jnp.asarray(_LADDER_CONSTS), dig_lm, *coords)
    return tuple(
        jnp.swapaxes(o, -1, -2)[:g].reshape(shape + (NLIMBS,)) for o in outs
    )
