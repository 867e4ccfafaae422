"""Operations and bytes the verify kernel's algorithm needs, from its shape.

Ed25519 verification as the program computes it (``pbft_tpu/crypto/
ed25519.py``): field elements are 32 limbs of 8 bits, so one field
multiplication is a 32 x 32 schoolbook product, 1,024 multiply-accumulates
of 8-bit limbs. Per signature:

- the joint ladder [S]B + [h](-A): 128 steps of two doublings (4M + 4S =
  8 multiplications each) and one unified addition (9): 128 x 25 = 3,200;
- its table of 16 entries: one doubling, one addition and one addition
  over 12 entries: 8 + 9 + 108 = 125;
- decompressing A: z^((p-5)/8) by the usual chain, 252 squarings and 12
  multiplications, and 6 more for u, v, v^3, v^7 and the check: 270;
- compressing the result: one inversion, 255 squarings and 12
  multiplications, and 2 more: 269.

3,864 field multiplications, 3,956,736 multiply-accumulates, counted as two
operations each. Carries, selects, the SHA-512 block and the mod-L
reduction are left out (under 3% of the multiply work), so the count is
a floor of what the kernel needs and the roofline share cannot be high by
it. Bytes: 128 in and 1 out per slot; the kernel is bound by compute.
"""

FIELD_MULS_PER_SIGNATURE = 3200 + 125 + 270 + 269
MACS_PER_FIELD_MUL = 32 * 32


def ed25519_verify(slots: int) -> dict:
    """Operations and bytes of one launch of ``slots`` padded slots."""
    return {
        "ops": 2 * MACS_PER_FIELD_MUL * FIELD_MULS_PER_SIGNATURE * slots,
        "bytes": 129 * slots,
    }
