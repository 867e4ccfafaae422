"""The Ed25519 hot path's three long multiply chains, held in VMEM.

Why these exist: the XLA pipeline in field.py/ed25519.py hands every field
multiply to the compiler as an HLO convolution of its own, its carries a
fusion of their own, so each of a verification's 3,864 products reads and
writes its (B, 32) operands through HBM and no value lives in a register
from one product to the next. These kernels run whole *chains* of products
out of VMEM, one ``pallas_call`` each:

- ``inv`` / ``pow_p58`` — the ~254-squaring exponent ladders of
  compress / decompress;
- ``ladder`` — the 128-step Shamir double-scalar ladder (two doublings and
  a table addition a step, some 82% of a verification), its 16-entry point
  table and its accumulator resident for all 128 steps.

Layout: **a limb is a whole vector register.** A tile is 1,024 signatures:
a field element is ``(32, 8, 128)`` int32, the limb index on the leading,
untiled axis and the batch on sublanes AND lanes. So ``a[i] * b[j]`` is one
multiply of 1,024 lanes with no rotate, no broadcast and no mask, the
38-fold is an index, and a carry is an ``and``, a shift and an add to the
NEXT register: every slice and concatenation in this file is on the leading
axis, where it names registers (or addresses) and moves nothing.
The wrappers transpose at the boundary ((B, 32) -> (32, B/128, 128), once a
chain), which XLA does.

The arithmetic (radix-2^8 signed limbs, the 38-fold at 2^256, 2- and 4-pass
vectorized carries, the chains of ``field._inv_chain``, the joint 2-bit
window) is field.py's to the bit: same bounds proof, same limbs out.
``tests/test_vmem_chains.py`` holds the in-kernel functions, which are
plain functions of arrays, to field.py / ed25519.py and the RFC 8032 oracle
on the CPU; ``tests/test_pallas_kernels.py`` (slow) runs the kernels under
the interpreter. Which compiled shapes take this path is
``ed25519.chains_for``: on a TPU, the shapes of 256 rows a chip or more (a
tile part full costs what a full one costs, and beats the XLA chains there).

Reference analogue: none — the reference left signature verification as
TODOs (src/behavior.rs:127, :185); this is the TPU-native centerpiece the
rebuild adds (SURVEY.md §5, §7).
"""

from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref
from .field import NLIMBS, RADIX, MASK, P, limbs_const, _inv_chain as _field_inv_chain

# One limb of one tile is one (8, 128) int32 vector register: 1,024 items.
SUBLANES = 8
LANES = 128
TILE = SUBLANES * LANES

_DTYPE = jnp.int32

# Tests flip this to run the kernels under the Pallas interpreter (minutes
# slow, CPU); nothing else does, and no environment variable reads it.
_INTERPRET = False

# Rows of the multiply's ``a`` operand worked through in one trip of its
# loop (a divisor of 32). The rows of a trip share their window of b, so
# more rows are fewer loads and fewer trips: 2 rows read 19.45 ms a
# 4,096-slot launch on the v5e's clock, 4 rows 14.05, 8 rows 12.41 (PR 43).
# They are also more code to trace and lower, which warm-up pays for every shape
# even when the compile cache answers (its key is the lowered module): at 8
# rows `setup_s` rose by 21-23 s of 90 with five cache hits, at 4 it is the
# parent's (PERF.md section 5).
_ROWS_PER_TRIP = 4

_C_2P = [int(v) for v in limbs_const(2 * P)]
_C_D2 = [int(v) for v in limbs_const(2 * ref.D % P)]
# [s]B for s = 0..3 in extended coordinates (ref.shamir_row0, the source of
# ed25519._ROW0 too), as the ladder kernel's scalar input: row 4*s + c holds
# the 32 limbs of coordinate c.
_ROW0 = np.stack(
    [limbs_const(v) for coords in ref.shamir_row0() for v in coords]
).astype(np.int32)  # (16, 32)


# ---------------------------------------------------------------------------
# Field arithmetic on (32, …) arrays, the limb on the LEADING axis: in a
# kernel (32, 8, 128), 32 vector registers. An operand is such an array, a
# ``_Held`` element of a ref (loaded where it is used), or, for a constant,
# a list of 32 Python ints. ``ws`` is the multiply's workspace, 96 limb
# slots of a VMEM ref, in a kernel; without one (the CPU tests) these are
# plain functions of arrays.
# ---------------------------------------------------------------------------


class _Held:
    """Element ``lead`` of a ref of stacked field elements: loaded where an
    operation uses it, not where the point was named (a value named early
    is 32 registers the compiler has to spill)."""

    def __init__(self, ref_, *lead):
        self.ref, self.lead = ref_, lead

    def load(self):
        return self.ref[self.lead] if self.lead else self.ref[...]


def _val(x, like=None):
    if isinstance(x, _Held):
        return x.load()
    if isinstance(x, list):  # a constant's limbs
        return jnp.concatenate([jnp.full((1,) + like.shape[1:], v, _DTYPE) for v in x])
    return x


def _carry(x, passes: int):
    """field.carry with the limb leading: the carry leaving limb k goes to
    limb k + 1 BY INDEX (a register's name, not a lane shift), the one
    leaving limb 31 re-enters limb 0 times 38."""
    for _ in range(passes):
        hi = x >> RADIX  # arithmetic shift: exact floor even for negatives
        x = (x & MASK) + jnp.concatenate([38 * hi[NLIMBS - 1 :], hi[: NLIMBS - 1]], axis=0)
    return x


def _staged(ws, at: int, rows):
    """Put ``rows`` (n, …) where a loop can slice them from a traced start,
    and give the slicer ``(start, n) -> rows[start : start + n]``: slots
    ``at``.. of the VMEM workspace in a kernel (a leading-axis start is
    address arithmetic), the array itself where there is no workspace."""
    if ws is None:
        return lambda start, n: lax.dynamic_slice_in_dim(rows, start, n, axis=0)
    ws[at : at + rows.shape[0]] = rows
    return lambda start, n: ws[pl.ds(at + start, n)]


def _mm(a, b, ws=None):
    """Field multiply, carried in, carried out; field.mul to the bit.

    out[n] = sum_i a_i * bx[32 - i + n] with bx = [38 * b | b]: row i of the
    schoolbook product lands on the 32 accumulators with its wrapped part
    (i + j >= 32, where 2^256 = 38 mod p) already scaled, so there is no
    fold pass and the accumulators are 32 registers, not 63. ``bx`` and
    ``a`` are staged because the loop slices them from ``i``. Bounds as
    field.mul: inputs |limb| < 2^10.3, columns < 32 * 38 * 2^20.6 < 2^30.9,
    inside int32."""
    square = a is b
    a = _val(a)
    b = a if square else _val(b, a)  # a constant is the second operand
    bx = _staged(ws, 0, jnp.concatenate([38 * b, b], axis=0))
    if square:
        a_rows = lambda start, n: bx(NLIMBS + start, n)
    else:
        a_rows = _staged(ws, 2 * NLIMBS, a)
    rows = _ROWS_PER_TRIP

    def trip(t, acc):
        # Rows i0 .. i0 + rows - 1 of a: row i0 + r reads bx[32 - i0 - r + n],
        # so the rows of one trip share a window of 32 + rows - 1 limbs of bx.
        i0 = t * rows
        window = bx(NLIMBS - (rows - 1) - i0, NLIMBS + rows - 1)
        ai = a_rows(i0, rows)
        for r in range(rows):
            acc = acc + ai[r : r + 1] * window[rows - 1 - r : rows - 1 - r + NLIMBS]
        return acc

    acc = lax.fori_loop(0, NLIMBS // rows, trip, jnp.zeros(b.shape, _DTYPE))
    return _carry(acc, 4)


def _sq(a, ws=None):
    return _mm(a, a, ws)


def _madd(a, b):
    return _carry(_val(a) + _val(b), 2)


def _msub(a, b):
    return _carry(_val(a) - _val(b), 2)


def _mneg(a):
    a = _val(a)
    return _carry(_val(_C_2P, a) - a, 2)


def _mul_small(a, k: int):
    return _carry(_val(a) * k, 4)


def _pow2k(x, k: int, ws=None):
    x = _val(x)
    if k <= 4:
        for _ in range(k):
            x = _sq(x, ws)
        return x
    return lax.fori_loop(0, k, lambda _, v: _sq(v, ws), x)


def _inv_chain(z, ws=None):
    """(z^(2^250-1), z^11): field._inv_chain run with the limb leading —
    the one chain definition every verifier backend shares."""
    return _field_inv_chain(
        _val(z),
        mul=lambda a, b: _mm(a, b, ws),
        sqr=lambda a: _sq(a, ws),
        pow2k=lambda x, k: _pow2k(x, k, ws),
    )


def _inv(z, ws=None):
    z_250_0, z11 = _inv_chain(z, ws)
    return _mm(_pow2k(z_250_0, 5, ws), z11, ws)


def _pow_p58(z, ws=None):
    z = _val(z)
    z_250_0, _ = _inv_chain(z, ws)
    return _mm(_pow2k(z_250_0, 2, ws), z, ws)


# ---------------------------------------------------------------------------
# Point arithmetic (a=-1 twisted Edwards, extended coordinates): a point is
# four operands as above, a result four arrays.
# ---------------------------------------------------------------------------


def _padd(p, q, ws=None):
    """add-2008-hwcd-3 — ed25519.point_add, product for product."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = _mm(_msub(y1, x1), _msub(y2, x2), ws)
    b = _mm(_madd(y1, x1), _madd(y2, x2), ws)
    c = _mm(_mm(t1, _C_D2, ws), t2, ws)
    d = _mul_small(_mm(z1, z2, ws), 2)
    e = _msub(b, a)
    f = _msub(d, c)
    g = _madd(d, c)
    h = _madd(b, a)
    return (_mm(e, f, ws), _mm(g, h, ws), _mm(f, g, ws), _mm(e, h, ws))


def _pdbl(p, ws=None):
    """dbl-2008-hwcd — ed25519.point_double, product for product."""
    x1, y1, z1, _ = p
    a = _sq(x1, ws)
    b = _sq(y1, ws)
    c = _mul_small(_sq(z1, ws), 2)
    d = _mneg(a)
    e = _msub(_msub(_sq(_madd(x1, y1), ws), a), b)
    g = _madd(d, b)
    f = _msub(g, c)
    h = _msub(d, b)
    return (_mm(e, f, ws), _mm(g, h, ws), _mm(f, g, ws), _mm(e, h, ws))


# ---------------------------------------------------------------------------
# Kernel bodies. Refs hold (…, 32, 8, 128) blocks of one tile.
# ---------------------------------------------------------------------------


def _point(ref_, *lead):
    return tuple(_Held(ref_, *lead, c) for c in range(4))


def _store_point(dst, lead, pt):
    for c in range(4):
        dst[(*lead, c)] = pt[c]


def _inv_kernel(z_ref, out_ref, ws):
    out_ref[...] = _inv(_Held(z_ref), ws)


def _p58_kernel(z_ref, out_ref, ws):
    out_ref[...] = _pow_p58(_Held(z_ref), ws)


def _ladder_kernel(row0_ref, dig_ref, a_ref, out_ref, tab_ref, ah_ref, sel_ref, ws):
    """The Shamir ladder of one tile: acc = 4 * acc + E[d_k] over 128 steps,
    E[s + 4h] = [s]B + [h](-A), d_k the k-th (MSB-first) pair of (S, h)
    2-bit digits as one int in 0..15 — ed25519.shamir_ladder's schedule.

    ``row0_ref`` (SMEM, (16, 32)): the limbs of [s]B, constants, so the four
    h = 0 entries are splats of scalars and hold no per-item copy.
    ``tab_ref`` (12, 4, 32, 8, 128): the entries that depend on A. ``ah_ref``:
    A, 2A, 3A. ``sel_ref``: the entry a step selected (and, while the table
    is built, [s]B as a full operand). ``out_ref`` is the accumulator: its
    block stays in VMEM until the tile's last step."""
    shape = (SUBLANES, LANES)

    def splat(v):
        return jnp.full(shape, v, _DTYPE)

    # A, 2A = dbl(A), 3A = 2A + A.
    ah_ref[0] = a_ref[...]
    _store_point(ah_ref, (1,), _pdbl(_point(ah_ref, 0), ws))
    _store_point(ah_ref, (2,), _padd(_point(ah_ref, 1), _point(ah_ref, 0), ws))

    # E[4h + s] = [s]B + [h](-A) for h = 1..3: twelve additions, one traced.
    def entry(j, _):
        h, s = j >> 2, j & 3

        def limb(k, _):
            for c in range(4):
                sel_ref[c, k] = splat(row0_ref[4 * s + c, k])
            return 0

        lax.fori_loop(0, NLIMBS, limb, 0)
        _store_point(tab_ref, (j,), _padd(_point(sel_ref), _point(ah_ref, h), ws))
        return 0

    lax.fori_loop(0, 12, entry, 0)

    out_ref[...] = jnp.zeros(out_ref.shape, _DTYPE)  # the identity: (0, 1, 1, 0)
    out_ref[1, 0] = splat(1)
    out_ref[2, 0] = splat(1)

    def step(k, _):
        d = dig_ref[k]  # (8, 128), 0..15

        def select(l, _):
            conds = [((d >> level) & 1) == 1 for level in range(4)]
            for c in range(4):
                cur = [splat(row0_ref[4 * s + c, l]) for s in range(4)]
                cur += [tab_ref[j, c, l] for j in range(12)]
                for cond in conds:  # halve: one select a pair a level
                    cur = [
                        jnp.where(cond, hi, lo)
                        for lo, hi in zip(cur[0::2], cur[1::2])
                    ]
                sel_ref[c, l] = cur[0]
            return 0

        lax.fori_loop(0, NLIMBS, select, 0)
        _store_point(out_ref, (), _pdbl(_point(out_ref), ws))
        _store_point(out_ref, (), _pdbl(_point(out_ref), ws))
        _store_point(out_ref, (), _padd(_point(out_ref), _point(sel_ref), ws))
        return 0

    lax.fori_loop(0, 128, step, 0)


# ---------------------------------------------------------------------------
# The wrappers: batch-major (B, k) <-> (k, B/128, 128) tiles, the batch
# padded to whole tiles, one pallas_call a chain, a grid step a tile.
# ---------------------------------------------------------------------------

_LIMB_BYTES = TILE * 4  # one limb of one tile: 4 KiB
_WS_SLOTS = 3 * NLIMBS

# VMEM the ladder holds a tile, in limbs of 4 KiB: the table 12 x 128, A's
# multiples 3 x 128, the selected entry 128, the workspace 96, and the
# pipeline's two buffers each of the digits (128), A (128) and the
# accumulator block (128): 2,528 limbs = 9.9 MiB, before what the compiler
# spills of a point operation's intermediates (eight elements, 1 MiB). That
# is over half of the 16 MiB a kernel is given by default on a v5e (of 128
# MiB), so the limit is raised to twice the count.
_LADDER_VMEM_LIMBS = (12 + 3 + 1) * 4 * NLIMBS + _WS_SLOTS + 2 * (128 + 2 * 4 * NLIMBS)
_LADDER_VMEM_LIMIT = 2 * _LADDER_VMEM_LIMBS * _LIMB_BYTES


def _rows(x) -> int:
    return math.prod(x.shape[:-1])


def _to_tiles(x, tiles: int):
    """(g, k) -> (k, tiles * 8, 128): column k of item r * 128 + lane."""
    g, k = x.shape
    x = jnp.pad(x, ((0, tiles * TILE - g), (0, 0)))
    return jnp.swapaxes(x, 0, 1).reshape(k, tiles * SUBLANES, LANES)


def _from_tiles(y, g: int):
    """(…, k, tiles * 8, 128) -> (…, g, k)."""
    k = y.shape[-3]
    flat = y.reshape(y.shape[:-3] + (k, -1))
    return jnp.swapaxes(flat, -1, -2)[..., :g, :]


def _tile_spec(*lead):
    """Grid step t's block of a (*lead, tiles * 8, 128) array: (*lead, 8, 128)."""
    zeros = (0,) * len(lead)
    return pl.BlockSpec(
        (*lead, SUBLANES, LANES), lambda t: (*zeros, t, 0), memory_space=pltpu.VMEM
    )


def _params(vmem_limit=None):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",), vmem_limit_bytes=vmem_limit
    )


@functools.partial(jax.jit, static_argnames=("kernel_name",))
def _run_chain(x, kernel_name: str):
    """Shared driver for the single-input chain kernels (inv, p58)."""
    kernel = {"inv": _inv_kernel, "p58": _p58_kernel}[kernel_name]
    g = _rows(x)
    tiles = -(-g // TILE)
    out = pl.pallas_call(
        kernel,
        grid=(tiles,),
        in_specs=[_tile_spec(NLIMBS)],
        out_specs=_tile_spec(NLIMBS),
        out_shape=jax.ShapeDtypeStruct((NLIMBS, tiles * SUBLANES, LANES), _DTYPE),
        scratch_shapes=[pltpu.VMEM((_WS_SLOTS, SUBLANES, LANES), _DTYPE)],
        compiler_params=_params(),  # 96 + 4 x 32 limbs: 0.9 MiB
        interpret=_INTERPRET,
        name=f"ed25519_{kernel_name}",
    )(_to_tiles(x.reshape(g, NLIMBS), tiles))
    return _from_tiles(out, g).reshape(x.shape)


def inv(z):
    """Drop-in for field.inv (z^(p-2), inv(0) = 0) as one kernel."""
    return _run_chain(z, kernel_name="inv")


def pow_p58(z):
    """Drop-in for field.pow_p58 (z^((p-5)/8)) as one kernel."""
    return _run_chain(z, kernel_name="p58")


@jax.jit
def ladder(s_bits, h_bits, a_neg):
    """Drop-in for ed25519.shamir_ladder: [S]B + [h](-A).

    s_bits, h_bits: (..., 256) int32 LSB-first; a_neg: point tuple with
    (..., 32) coords. Returns the accumulator point, batch-major."""
    shape = s_bits.shape[:-1]
    g = _rows(s_bits)
    tiles = -(-g // TILE)

    # Digit schedule, MSB-first: step k consumes bit-pair 127-k of each
    # scalar -> d = s0 + 2 s1 + 4 h0 + 8 h1 in 0..15.
    sb = s_bits.reshape(g, 256)
    hb = h_bits.reshape(g, 256)
    dig = sb[:, 0::2] + 2 * sb[:, 1::2] + 4 * hb[:, 0::2] + 8 * hb[:, 1::2]
    dig = _to_tiles(dig[:, ::-1], tiles)  # (128, tiles * 8, 128)
    a = jnp.stack([_to_tiles(c.reshape(g, NLIMBS), tiles) for c in a_neg])

    point = (4, NLIMBS, SUBLANES, LANES)
    out = pl.pallas_call(
        _ladder_kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _tile_spec(128),
            _tile_spec(4, NLIMBS),
        ],
        out_specs=_tile_spec(4, NLIMBS),
        out_shape=jax.ShapeDtypeStruct(
            (4, NLIMBS, tiles * SUBLANES, LANES), _DTYPE
        ),
        scratch_shapes=[
            pltpu.VMEM((12,) + point, _DTYPE),
            pltpu.VMEM((3,) + point, _DTYPE),
            pltpu.VMEM(point, _DTYPE),
            pltpu.VMEM((_WS_SLOTS, SUBLANES, LANES), _DTYPE),
        ],
        compiler_params=_params(_LADDER_VMEM_LIMIT),
        interpret=_INTERPRET,
        name="ed25519_ladder",
    )(jnp.asarray(_ROW0), dig, a)
    coords = _from_tiles(out, g)  # (4, g, 32)
    return tuple(coords[c].reshape(shape + (NLIMBS,)) for c in range(4))
