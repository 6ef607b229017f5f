"""Number formats from the paper.

Table 3 reduced-precision floating-point formats (all carry a sign bit and
mimic IEEE 754 incl. +/-inf, NaN and subnormals), plus narrow two's
complement / unsigned integers, plus the 4-bit *slice* arithmetic used by
the register allocator (Section 3.2: a 32-bit register = 8 slices).

Everything here is pure bit arithmetic on uint32 carriers implemented with
jax.numpy so it can run inside jit, inside Pallas kernel bodies, and under
vmap. These functions are the *reference semantics*; the Pallas kernels in
``repro.kernels`` implement the same math tiled for TPU VMEM.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Dict, Tuple

import jax.numpy as jnp
import numpy as np

SLICE_BITS = 4                      # Section 3.2: slices are 4 bits
REGISTER_BITS = 32                  # one physical (thread) register
SLICES_PER_REGISTER = REGISTER_BITS // SLICE_BITS   # = 8


@dataclasses.dataclass(frozen=True)
class FloatFormat:
    """An IEEE-754-like float format: 1 sign + exp_bits + mantissa_bits."""

    name: str
    exp_bits: int
    mantissa_bits: int

    @property
    def total_bits(self) -> int:
        return 1 + self.exp_bits + self.mantissa_bits

    @property
    def bias(self) -> int:
        return (1 << (self.exp_bits - 1)) - 1

    @property
    def max_biased_exp(self) -> int:
        return (1 << self.exp_bits) - 1

    @property
    def max_finite(self) -> float:
        """Largest finite magnitude the format represents: the overflow
        threshold the static activation-width analysis proves bounds
        against (a planned width whose max_finite is below a value's
        proven magnitude bound would silently clip to inf)."""
        m = self.mantissa_bits
        return float((2.0 - 2.0 ** -m) * 2.0 ** (self.max_biased_exp - 1
                                                 - self.bias))

    @property
    def slices(self) -> int:
        return slices_for_bits(self.total_bits)

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"{self.name}(e{self.exp_bits}m{self.mantissa_bits})"


# Table 3: total bits -> (exponent bits, mantissa bits); sign bit implied.
FLOAT_FORMATS: Dict[int, FloatFormat] = {
    32: FloatFormat("AF32", 8, 23),   # IEEE single precision
    28: FloatFormat("AF28", 7, 20),
    24: FloatFormat("AF24", 6, 17),
    20: FloatFormat("AF20", 5, 14),
    16: FloatFormat("AF16", 5, 10),   # IEEE half precision
    12: FloatFormat("AF12", 4, 7),
    8: FloatFormat("AF8", 3, 4),
}
# Sorted narrowest-first: the precision-tuning search walks this ladder.
FLOAT_LADDER: Tuple[int, ...] = (8, 12, 16, 20, 24, 28, 32)

F32 = FLOAT_FORMATS[32]


def ladder_snap(bits: int, below: bool = False) -> int:
    """Widest Table 3 rung <= ``bits`` (strictly < with ``below``),
    floored at the narrowest rung — the shared snap used by plan
    derivation and the speculative draft-width resolution."""
    rungs = [r for r in FLOAT_LADDER if (r < bits if below else r <= bits)]
    return rungs[-1] if rungs else FLOAT_LADDER[0]

_U32 = jnp.uint32
_ONE = np.uint32(1)


def slices_for_bits(bits: int) -> int:
    """Number of 4-bit slices needed for an operand of ``bits`` bits."""
    if bits <= 0:
        raise ValueError(f"operand width must be positive, got {bits}")
    return -(-bits // SLICE_BITS)


def round_bits_to_slice(bits: int) -> int:
    """Round a bitwidth up to the 4-bit slice granularity of Section 3.2."""
    return slices_for_bits(bits) * SLICE_BITS


def int_bits_needed(lo: int, hi: int) -> Tuple[int, bool]:
    """Minimal (bits, signed) to represent every integer in [lo, hi].

    Mirrors the last step of the static range analysis (Fig. 8d): unsigned
    when lo >= 0, otherwise two's complement.
    """
    if lo > hi:
        raise ValueError(f"empty range [{lo}, {hi}]")
    if lo >= 0:
        bits = max(int(hi).bit_length(), 1)
        return bits, False
    # two's complement: need bits s.t. -(2^(b-1)) <= lo and hi <= 2^(b-1)-1
    b = 1
    while not (-(1 << (b - 1)) <= lo and hi <= (1 << (b - 1)) - 1):
        b += 1
    return b, True


# ---------------------------------------------------------------------------
# f32 <-> uint32 bit views
# ---------------------------------------------------------------------------

def f32_to_bits(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.asarray(x, jnp.float32).view(_U32)


def bits_to_f32(u: jnp.ndarray) -> jnp.ndarray:
    return jnp.asarray(u, _U32).view(jnp.float32)


# ---------------------------------------------------------------------------
# Encode: f32 -> narrow float code (the Value Truncator's step 1, Fig. 5)
# ---------------------------------------------------------------------------

def encode_float(x: jnp.ndarray, fmt: FloatFormat) -> jnp.ndarray:
    """Convert float32 values to ``fmt`` codes (uint32, low total_bits valid).

    Round-to-nearest-even; preserves signed zero, +/-inf and NaN; produces
    subnormals on underflow and inf on overflow, exactly like an IEEE
    narrowing conversion. AF32 is the identity on bit patterns.
    """
    u = f32_to_bits(x)
    if fmt.total_bits == 32:
        return u

    e_t, m_t = fmt.exp_bits, fmt.mantissa_bits
    shift = 23 - m_t                       # mantissa bits to drop
    sign = (u >> np.uint32(31)) & _ONE
    exp = (u >> np.uint32(23)) & np.uint32(0xFF)
    man = u & np.uint32(0x7FFFFF)

    # Unbiased exponent, rebias into the target format.
    e_unb = exp.astype(jnp.int32) - 127
    e_new = e_unb + fmt.bias               # tentative biased target exponent

    # --- normal path: RNE-round the mantissa from 23 -> m_t bits ----------
    def _rne(value: jnp.ndarray, k: int) -> jnp.ndarray:
        """Round value (uint32) right by k bits, round-to-nearest-even."""
        if k == 0:
            return value
        kept = value >> np.uint32(k)
        round_bit = (value >> np.uint32(k - 1)) & _ONE
        sticky = jnp.where(
            (value & np.uint32((1 << (k - 1)) - 1)) != 0, _ONE, np.uint32(0)
        ) if k > 1 else np.uint32(0) * value
        lsb = kept & _ONE
        inc = round_bit & (sticky | lsb)
        return kept + inc

    man_rounded = _rne(man, shift)
    # Mantissa overflow on rounding (e.g. 0x7FFFFF -> 1.0 x 2^(e+1)).
    man_carry = man_rounded >> np.uint32(m_t)
    e_norm = e_new + man_carry.astype(jnp.int32)
    man_norm = jnp.where(man_carry > 0, np.uint32(0), man_rounded)

    # --- subnormal path: target exponent underflowed (e_new <= 0) ---------
    # value = 1.man * 2^(e_unb); as target subnormal: 0.man' * 2^(1-bias)
    # mantissa' = (1.man) >> (1 - e_new), RNE over the *full* shifted range.
    full = man | np.uint32(1 << 23)        # implicit leading one, 24 bits
    # Total right-shift from the 24-bit significand down to the target
    # subnormal position. full < 2^24, so any shift >= 24 keeps nothing;
    # clip to 31 to stay within defined uint32 shift range (sticky below
    # still sees every dropped bit because the mask covers bits 0..30).
    sub_shift = jnp.clip((1 - e_new) + shift, 0, 31)
    # Per-element variable shift with RNE: compute kept/round/sticky lanes.
    kept = full >> sub_shift.astype(_U32)
    rb_pos = jnp.maximum(sub_shift - 1, 0).astype(_U32)
    round_bit = jnp.where(sub_shift > 0, (full >> rb_pos) & _ONE, np.uint32(0))
    below_mask = jnp.where(
        sub_shift > 1,
        (_ONE << jnp.maximum(sub_shift - 1, 1).astype(_U32)) - _ONE,
        np.uint32(0),
    )
    sticky = jnp.where((full & below_mask) != 0, _ONE, np.uint32(0))
    inc = round_bit & (sticky | (kept & _ONE))
    man_sub = kept + inc
    # A subnormal that rounds up to 1 << m_t becomes the smallest normal:
    sub_to_norm = man_sub >> np.uint32(m_t)
    e_sub = sub_to_norm.astype(jnp.int32)          # 0 stays subnormal
    man_sub = jnp.where(sub_to_norm > 0, np.uint32(0), man_sub)
    # Shifts beyond 24+shift bits flush to (signed) zero automatically.

    is_sub = e_new <= 0
    e_out = jnp.where(is_sub, e_sub, e_norm)
    man_out = jnp.where(is_sub, man_sub, man_norm)

    # --- overflow to inf ---------------------------------------------------
    overflow = e_out >= fmt.max_biased_exp
    e_out = jnp.where(overflow, fmt.max_biased_exp, e_out)
    man_out = jnp.where(overflow, np.uint32(0), man_out)

    # --- source inf / NaN ---------------------------------------------------
    src_special = exp == np.uint32(0xFF)
    src_nan = src_special & (man != 0)
    e_out = jnp.where(src_special, fmt.max_biased_exp, e_out)
    man_out = jnp.where(
        src_special,
        jnp.where(src_nan, np.uint32(1 << (m_t - 1)), np.uint32(0)),
        man_out,
    )
    # --- source zero / subnormal (e_unb == -127): f32 subnormals are far
    # below every target's subnormal range (min target m_t=4, bias<=15
    # for e<=5... actually AF20/AF16 bias 15 -> min subnormal 2^-24), so
    # flushing them to signed zero is exact for all Table 3 targets except
    # AF32 (identity, handled above). AF28 (bias 63): min f32 subnormal
    # 2^-149 << 2^-(62+20); flush is the correctly rounded result.
    src_zero = exp == 0
    e_out = jnp.where(src_zero, 0, e_out)
    man_out = jnp.where(src_zero, np.uint32(0), man_out)

    code = (
        (sign << np.uint32(fmt.total_bits - 1))
        | (e_out.astype(_U32) << np.uint32(m_t))
        | (man_out & np.uint32((1 << m_t) - 1))
    )
    return code


# ---------------------------------------------------------------------------
# Decode: narrow float code -> f32 (the Value Converter, Section 3.2.5)
# ---------------------------------------------------------------------------

def decode_float(code: jnp.ndarray, fmt: FloatFormat) -> jnp.ndarray:
    """Expand ``fmt`` codes to float32. Exact (widening) conversion."""
    code = jnp.asarray(code, _U32)
    if fmt.total_bits == 32:
        return bits_to_f32(code)

    m_t = fmt.mantissa_bits
    sign = (code >> np.uint32(fmt.total_bits - 1)) << np.uint32(31)
    mag = code & np.uint32((1 << (fmt.total_bits - 1)) - 1)  # exp | man
    exp = mag >> np.uint32(m_t)
    man = mag & np.uint32((1 << m_t) - 1)

    # Normals: left-align exp|man under the f32 fields, then rebias the
    # exponent with one add (no carry: the rebiased exponent stays < 255).
    normal = (mag << np.uint32(23 - m_t)) + np.uint32((127 - fmt.bias) << 23)
    # Subnormals and zeros: value = man * 2^(1 - bias - m_t), exact in f32
    # (an integer below 2^m_t times a power of two that stays normal), so
    # no leading-one search and no f32 subnormal the TPU would flush.
    sub = f32_to_bits(man.astype(jnp.int32).astype(jnp.float32)
                      * np.float32(2.0 ** (1 - fmt.bias - m_t)))
    special = np.uint32(0x7F800000) | jnp.where(
        man != 0, np.uint32(1 << 22), np.uint32(0))

    out = jnp.where(exp == 0, sub,
                    jnp.where(exp == np.uint32(fmt.max_biased_exp),
                              special, normal))
    return bits_to_f32(out | sign)


# ---------------------------------------------------------------------------
# Narrow integers (Section 4.2 output): two's complement / unsigned codes
# ---------------------------------------------------------------------------

def encode_int(x: jnp.ndarray, bits: int, signed: bool) -> jnp.ndarray:
    """Truncate int32 values to ``bits``-bit codes (uint32 carrier)."""
    if not (1 <= bits <= 32):
        raise ValueError(f"bits must be in [1, 32], got {bits}")
    mask = np.uint32((1 << bits) - 1) if bits < 32 else np.uint32(0xFFFFFFFF)
    del signed  # encoding is the same; signedness matters on decode
    return jnp.asarray(x).astype(jnp.int32).view(_U32) & mask


def decode_int(code: jnp.ndarray, bits: int, signed: bool) -> jnp.ndarray:
    """Sign- or zero-extend ``bits``-bit codes back to int32 (the TVE's
    2:1 padding mux of Fig. 4: zeros for unsigned, sign extension else)."""
    code = jnp.asarray(code, _U32)
    if bits == 32:
        return code.view(jnp.int32)
    mask = np.uint32((1 << bits) - 1)
    code = code & mask
    if not signed:
        return code.astype(jnp.int32)
    sbit = np.uint32(1 << (bits - 1))
    return ((code ^ sbit).view(jnp.int32) - jnp.int32(sbit)).astype(jnp.int32)


@lru_cache(maxsize=None)
def format_for_bits(bits: int) -> FloatFormat:
    """The Table 3 format with the given total width."""
    if bits not in FLOAT_FORMATS:
        raise ValueError(
            f"no Table 3 float format with {bits} bits; choose from "
            f"{sorted(FLOAT_FORMATS)}"
        )
    return FLOAT_FORMATS[bits]


def narrowest_at_least(bits: int) -> FloatFormat:
    """Narrowest Table 3 format with total_bits >= bits."""
    for b in FLOAT_LADDER:
        if b >= bits:
            return FLOAT_FORMATS[b]
    return F32
