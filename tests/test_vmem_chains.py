"""The VMEM chains' arithmetic, on the CPU and without the interpreter.

``pallas_kernels._mm`` / ``_carry`` / ``_padd`` / ``_pdbl`` and the chains
built of them are plain functions of arrays whose LEADING axis is the limb
(the layout of a tile: a limb a vector register): given no VMEM workspace
they slice their staged operands from arrays and are otherwise the code a
kernel traces. So they are held here to ``field.py`` limb for limb, to
``ed25519.point_add`` / ``point_double`` and to the RFC 8032 oracle: a
divergence between the two lowerings would split replicas. Also the rule
that says which compiled shapes take the chains (``ed25519.chains_for``),
as the pure function it is.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pbft_tpu.crypto import ed25519 as E
from pbft_tpu.crypto import field as F
from pbft_tpu.crypto import pallas_kernels as PK
from pbft_tpu.crypto import ref

_RNG = np.random.default_rng(0x43)
_TILE = (2, 4)  # stands for (8, 128): the functions never look at it


def _lead(x):
    """(..., 32) batch-major -> (32, ...): the limb on the leading axis."""
    return jnp.moveaxis(jnp.asarray(x, jnp.int32), -1, 0)


def _back(x):
    """A result, (32, ...) -> (..., 32) numpy."""
    return np.moveaxis(np.asarray(x), 0, -1)


def _jit(fn):
    """One compiled program a shape, and a fresh one a call of ``_jit``
    (``_ROWS_PER_TRIP`` is read when the multiply is traced)."""
    return jax.jit(lambda *args: fn(*args))


_MM, _SQ, _PADD, _PDBL = map(_jit, (PK._mm, PK._sq, PK._padd, PK._pdbl))
_MADD, _MSUB, _MNEG = map(_jit, (PK._madd, PK._msub, PK._mneg))
_F_MUL = [jax.jit(F._mul_schoolbook), jax.jit(F._mul_conv)]


def _const(v: int):
    return np.broadcast_to(F.limbs_const(v % F.P), _TILE + (F.NLIMBS,))


def _alternating():
    row = np.array([1023 if i % 2 else -1023 for i in range(F.NLIMBS)], np.int32)
    return np.broadcast_to(row, _TILE + (F.NLIMBS,))


# Operands in batch-major form, (2, 4, 32) each. "loose" is the bound of
# tests/test_field.py's hostile case: every limb at the loosest magnitude
# add/sub can produce.
OPERANDS = {
    "random": lambda: _RNG.integers(-1023, 1024, size=_TILE + (F.NLIMBS,)),
    "loose_plus": lambda: np.full(_TILE + (F.NLIMBS,), 1023),
    "loose_minus": lambda: np.full(_TILE + (F.NLIMBS,), -1023),
    "loose_alternating": _alternating,
    "zero": lambda: _const(0),
    "one": lambda: _const(1),
    "p_minus_1": lambda: _const(F.P - 1),
}


def _ints(x):
    return [F.limbs_to_int(row) % F.P for row in np.asarray(x).reshape(-1, F.NLIMBS)]


@pytest.mark.parametrize("b_kind", sorted(OPERANDS))
@pytest.mark.parametrize("a_kind", sorted(OPERANDS))
def test_mm_is_field_mul_limb_for_limb(a_kind, b_kind):
    a, b = OPERANDS[a_kind](), OPERANDS[b_kind]()
    got = _back(_MM(_lead(a), _lead(b)))
    for impl in _F_MUL:
        np.testing.assert_array_equal(
            got, np.asarray(impl(jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32)))
        )
    # ... and the big-int ground truth: no int32 overflow in the columns.
    want = [x * y % F.P for x, y in zip(_ints(a), _ints(b))]
    assert _ints(np.asarray(F.canon(jnp.asarray(got)))) == want


@pytest.mark.parametrize("kind", sorted(OPERANDS))
def test_square_and_constant_operand(kind):
    """``_mm(a, a)`` stages one operand; a constant operand is a list of Python ints."""
    a = OPERANDS[kind]()
    aj = jnp.asarray(a, jnp.int32)
    lead = _lead(a)
    np.testing.assert_array_equal(_back(_SQ(lead)), np.asarray(_F_MUL[0](aj, aj)))
    np.testing.assert_array_equal(
        _back(_jit(lambda x: PK._mm(x, PK._C_D2))(lead)),
        np.asarray(_F_MUL[0](aj, jnp.asarray(E._D2))),
    )


@pytest.mark.parametrize("rows", [1, 2, 4, 8, 32])
def test_mm_is_the_same_whatever_the_rows_a_trip(monkeypatch, rows):
    monkeypatch.setattr(PK, "_ROWS_PER_TRIP", rows)
    a, b = OPERANDS["random"](), OPERANDS["loose_alternating"]()
    np.testing.assert_array_equal(
        _back(_jit(PK._mm)(_lead(a), _lead(b))),
        np.asarray(_F_MUL[0](jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32))),
    )


@pytest.mark.parametrize("passes", [2, 4])
@pytest.mark.parametrize("kind", ["random", "loose_plus", "loose_minus", "p_minus_1"])
def test_carry_and_the_small_ops_are_field_py_s(kind, passes):
    a = OPERANDS[kind]()
    b = OPERANDS["random"]()
    aj, bj = jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32)
    wide = aj * 1000 + bj  # what a carry pass is for
    np.testing.assert_array_equal(
        _back(PK._carry(_lead(wide), passes)), np.asarray(F.carry(wide, passes))
    )
    np.testing.assert_array_equal(_back(_MADD(_lead(a), _lead(b))), np.asarray(F.add(aj, bj)))
    np.testing.assert_array_equal(_back(_MSUB(_lead(a), _lead(b))), np.asarray(F.sub(aj, bj)))
    np.testing.assert_array_equal(_back(_MNEG(_lead(a))), np.asarray(F.neg(aj)))
    np.testing.assert_array_equal(
        _back(PK._mul_small(_lead(a), 2)), np.asarray(F.mul_small(aj, 2))
    )


def _points(n):
    """n curve points in extended coordinates (Z = 1), batch-major (n, 32)
    each, and the same as the oracle's affine pairs."""
    pts = [ref.scalar_mult(int(k), ref.BASE) for k in _RNG.integers(1, 2**62, size=n)]
    ext = [(x, y, 1, x * y % F.P) for x, y in pts]
    coords = tuple(
        jnp.asarray(np.stack([F.limbs_const(p[c]) for p in ext]), jnp.int32)
        for c in range(4)
    )
    return coords, pts


def _affine(coords):
    x, y, z, _ = (_ints(np.asarray(F.canon(c))) for c in coords)
    return [
        (xi * pow(zi, F.P - 2, F.P) % F.P, yi * pow(zi, F.P - 2, F.P) % F.P)
        for xi, yi, zi in zip(x, y, z)
    ]


@pytest.mark.parametrize("op", ["add", "double", "add_identity", "add_same"])
def test_point_ops_are_ed25519_py_s_and_the_oracle_s(op):
    (p, p_ref), (q, q_ref) = _points(3), _points(3)
    if op == "add_identity":
        q, q_ref = E.identity((3,)), [(0, 1)] * 3
    if op == "add_same":  # the unified addition is complete: P + P too
        q, q_ref = p, p_ref
    lead = lambda pt: tuple(_lead(c) for c in pt)
    if op == "double":
        got = _PDBL(lead(p))
        want = jax.jit(E.point_double)(p)
        oracle = [ref.point_add(a, a) for a in p_ref]
    else:
        got = _PADD(lead(p), lead(q))
        want = jax.jit(E.point_add)(p, q)
        oracle = [ref.point_add(a, b) for a, b in zip(p_ref, q_ref)]
    got = tuple(jnp.asarray(_back(c)) for c in got)
    for g, w in zip(got, want):  # limb for limb, all four coordinates
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert _affine(got) == oracle


@pytest.mark.parametrize("chain", ["inv", "pow_p58"])
def test_the_exponent_chains_are_field_py_s(chain):
    x = np.concatenate(
        [OPERANDS["random"]().reshape(-1, F.NLIMBS)[:3], _const(0)[0, :1], _const(1)[0, :1],
         _const(F.P - 1)[0, :1]]
    )
    xj = jnp.asarray(x, jnp.int32)
    fn, want_fn, e = {
        "inv": (PK._inv, F.inv, F.P - 2),
        "pow_p58": (PK._pow_p58, F.pow_p58, (F.P - 5) // 8),
    }[chain]
    got = _back(jax.jit(fn)(_lead(x)))
    np.testing.assert_array_equal(got, np.asarray(jax.jit(want_fn)(xj)))
    assert _ints(np.asarray(F.canon(jnp.asarray(got)))) == [pow(v, e, F.P) for v in _ints(x)]


@pytest.mark.parametrize(
    "backend, rows, want",
    [("tpu", 16, "xla"), ("tpu", 64, "xla"), ("tpu", 255, "xla"), ("tpu", 256, "vmem"),
     ("tpu", 1024, "vmem"), ("tpu", 4096, "vmem"), ("cpu", 16, "xla"), ("cpu", 256, "xla"),
     ("cpu", 1024, "xla"), ("cpu", 4096, "xla"), ("gpu", 4096, "xla")],
)
def test_the_shape_rule_is_a_pure_function_of_backend_and_rows(monkeypatch, backend, rows, want):
    # Nothing in the environment moves it.
    monkeypatch.setenv("PBFT_PALLAS", "1")
    monkeypatch.setenv("PBFT_FIELD_MUL", "schoolbook")
    assert E.chains_for(rows, backend) == want
    # A tile is 1,024 items and costs the same a quarter full as full: the
    # 256-slot shape runs on one (3.61 ms on the device against the XLA
    # chains' 5.318, PERF.md section 5, PR 43), the 16- and 64-slot shapes do
    # not (the serving table sends their windows to the 256-slot program).
    assert E.VMEM_CHAIN_ROWS == 256 and PK.TILE == 1024
    # The default backend is what the process runs on: a CPU here.
    assert E.chains_for(rows) == "xla"
    assert not E._use_pallas(jnp.zeros((rows, 32), jnp.uint8))


def test_the_tile_layout_round_trips_and_pads():
    x = jnp.asarray(_RNG.integers(0, 255, size=(1500, 32)), jnp.int32)
    tiles = PK._to_tiles(x, 2)
    assert tiles.shape == (32, 2 * PK.SUBLANES, PK.LANES)
    # limb k of item r * 128 + lane sits at [k, r, lane]; pad rows are zeros
    assert int(tiles[5, 3, 17]) == int(x[3 * 128 + 17, 5])
    assert not np.asarray(tiles[:, 12:, :]).any() and not np.asarray(tiles[:, 11, 92:]).any()
    np.testing.assert_array_equal(np.asarray(PK._from_tiles(tiles, 1500)), np.asarray(x))
    # The ladder's scalar input: the limbs of [s]B, coordinate by coordinate.
    assert PK._ROW0.shape == (16, 32)
    for s in range(4):
        for c in range(4):
            np.testing.assert_array_equal(PK._ROW0[4 * s + c], E._ROW0[c][s])
    # What the ladder asks of VMEM, against what a v5e kernel is given.
    assert 8 * 2**20 < PK._LADDER_VMEM_LIMBS * 4096 < 16 * 2**20 < PK._LADDER_VMEM_LIMIT <= 32 * 2**20


# -- the chip's own compiler, without the chip --------------------------------------------
#
# The TPU's compiler is installed here and compiles for a chip that is
# described, not attached: what Mosaic refuses (a slice off the tiling, more
# VMEM than a kernel may use) it refuses here, in seconds, at the real tile.
# Nothing runs, so this says nothing about results or times.


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    # An entry compiled for a described chip is written but cannot be read
    # back without one: keep these compiles out of the persistent cache.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("chain, rows", [("inv", 1024), ("pow_p58", 4096), ("ladder", 1024)])
def test_the_chip_s_compiler_takes_the_kernels(one_chip, no_compile_cache, chain, rows):
    elem = jax.ShapeDtypeStruct((rows, F.NLIMBS), jnp.int32, sharding=one_chip)
    if chain == "ladder":
        bits = jax.ShapeDtypeStruct((rows, 256), jnp.int32, sharding=one_chip)
        args = (bits, bits, (elem,) * 4)
    else:
        args = (elem,)
    lowered = jax.jit(getattr(PK, chain)).lower(*args)
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") == 1  # ONE kernel a chain
    # The witness the engine records (per_shape[].chains, fused) reads it there.
    from pbft_tpu.parallel import chains_of

    assert chains_of(compiled) == "vmem"
    assert "tpu_custom_call" in lowered.as_text()  # where it reads if an executable gives no text
