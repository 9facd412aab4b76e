"""Integer-only fixed-point arithmetic (gemmlowp semantics) in JAX.

This module is the numerical heart of the paper "On the quantization of
recurrent neural networks" (Li & Alvarez, 2021): every op here is expressible
with 32-bit integer ALU instructions (add/sub/mul/shift/compare/select) so the
same code runs on CPUs, DSPs, integer neural accelerators, and -- via Pallas --
on TPU VPU lanes.  No floating point is used anywhere in the traced paths.

Notation: ``Q_{m.n}`` is a signed fixed-point format with ``m`` integer bits
and ``n`` fractional bits (m + n + 1 == bit width).  A raw int32 ``r`` in
``Q_{m.(31-m)}`` represents the real value ``r * 2**(m-31)``.

Key primitives (bit-exact ports of gemmlowp/fixedpoint.h and the TFLite
quantized-LSTM kernel semantics):

* ``saturating_rounding_doubling_high_mul`` (SRDHM) -- the fixed-point multiply.
* ``rounding_divide_by_pot`` -- rounding arithmetic right shift.
* ``multiply_by_quantized_multiplier`` -- rescale by a statically-derived
  (mantissa, exponent) pair; the only place real-valued scales enter the
  integer graph, and they enter as *static* integers computed offline.
* ``exp_on_negative_values`` / ``tanh_fp`` / ``sigmoid_fp`` -- integer
  transcendentals via barrel-shifted exponentials and Newton-Raphson division.
* ``integer_rsqrt_multiplier`` / ``integer_recip_multiplier`` -- integer
  Newton-Raphson 1/sqrt(V) and 1/x used by integer LayerNorm/RMSNorm/softmax.

TPU adaptation (see DESIGN.md): TFLite's reference kernels accumulate LayerNorm
statistics in int64; TPUs have no 64-bit integer datapath, so everywhere a u64
is required we carry (hi, lo) uint32 limb pairs instead.  The math stays exact.

The pure-numpy oracle lives in ``repro/kernels/ref.py`` and
``tests/test_fixedpoint.py`` cross-checks against python big-int arithmetic.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

INT32_MAX = 2147483647
INT32_MIN = -2147483648
INT16_MAX = 32767
INT16_MIN = -32768

# ---------------------------------------------------------------------------
# u64-as-two-uint32-limbs helpers.
# ---------------------------------------------------------------------------


def _u32(x):
    return jnp.asarray(x).astype(jnp.uint32)


def _i32(x):
    return jnp.asarray(x).astype(jnp.int32)


def u64_from_mul_u32(a, b) -> Tuple[jax.Array, jax.Array]:
    """Full 64-bit product of two uint32 values as (hi, lo) uint32 limbs."""
    a = _u32(a)
    b = _u32(b)
    mask = jnp.uint32(0xFFFF)
    a_hi, a_lo = a >> 16, a & mask
    b_hi, b_lo = b >> 16, b & mask
    ll = a_lo * b_lo  # < 2**32, exact in uint32
    lh = a_lo * b_hi  # < 2**32
    hl = a_hi * b_lo  # < 2**32
    hh = a_hi * b_hi  # < 2**32
    mid = lh + hl  # may wrap once: carry weight 2**(32+16)
    carry_mid = (mid < lh).astype(jnp.uint32)
    lo = ll + ((mid & mask) << 16)
    carry_lo = (lo < ll).astype(jnp.uint32)
    hi = hh + (mid >> 16) + (carry_mid << 16) + carry_lo
    return hi, lo


def u64_add(h1, l1, h2, l2) -> Tuple[jax.Array, jax.Array]:
    lo = _u32(l1) + _u32(l2)
    carry = (lo < _u32(l1)).astype(jnp.uint32)
    return _u32(h1) + _u32(h2) + carry, lo


def u64_sub(h1, l1, h2, l2) -> Tuple[jax.Array, jax.Array]:
    lo = _u32(l1) - _u32(l2)
    borrow = (_u32(l1) < _u32(l2)).astype(jnp.uint32)
    return _u32(h1) - _u32(h2) - borrow, lo


def u64_shift_right(hi, lo, n: int) -> Tuple[jax.Array, jax.Array]:
    """Logical right shift of a u64 limb pair by a static 0 <= n < 32."""
    if n == 0:
        return _u32(hi), _u32(lo)
    hi = _u32(hi)
    lo = _u32(lo)
    return hi >> n, (lo >> n) | (hi << (32 - n))


def u64_mul_small(hi, lo, k: int) -> Tuple[jax.Array, jax.Array]:
    """(hi, lo) * k for a static 0 <= k < 2**16; exact provided no overflow."""
    hi = _u32(hi)
    lo = _u32(lo)
    ku = jnp.uint32(k)
    h1, l1 = u64_from_mul_u32(lo, ku)
    return h1 + hi * ku, l1


def clz32(x) -> jax.Array:
    """Leading zeros of a uint32 (returns 32 for x == 0); vectorized."""
    x = _u32(x)
    n = jnp.zeros(jnp.shape(x), jnp.int32)
    cur = x
    for shift in (16, 8, 4, 2, 1):
        hi = cur >> shift
        take = hi != jnp.uint32(0)
        cur = jnp.where(take, hi, cur)
        n = n + jnp.where(take, jnp.int32(shift), jnp.int32(0))
    # n == floor(log2(x)) for x != 0.
    return jnp.where(x == jnp.uint32(0), jnp.int32(32), jnp.int32(31) - n)


def u64_leading_zeros(hi, lo) -> jax.Array:
    return jnp.where(_u32(hi) == 0, 32 + clz32(lo), clz32(hi))


# ---------------------------------------------------------------------------
# gemmlowp core ops
# ---------------------------------------------------------------------------


def saturating_rounding_doubling_high_mul(a, b) -> jax.Array:
    """Bit-exact gemmlowp SRDHM: trunc((2*a*b + nudge) / 2**31), saturated.

    Both operands are int32; viewing them as Q0.31 the result is the rounded
    Q0.31 product.  Implemented with 32-bit limb arithmetic only (no int64).
    """
    a = _i32(a)
    b = _i32(b)
    overflow = jnp.logical_and(a == INT32_MIN, b == INT32_MIN)
    neg = (a < 0) ^ (b < 0)
    # |a| as uint32 (INT32_MIN's magnitude 2**31 is representable in uint32).
    abs_a = jnp.where(a < 0, jnp.uint32(0) - _u32(a), _u32(a))
    abs_b = jnp.where(b < 0, jnp.uint32(0) - _u32(b), _u32(b))
    hi, lo = u64_from_mul_u32(abs_a, abs_b)  # |a*b| <= 2**62
    # gemmlowp: (2ab + nudge) / 2**31 with C truncating division and
    # nudge = ab >= 0 ? 2**30 : 1 - 2**30.  On the magnitude this becomes
    # mag = (2|ab| + n) >> 31 with n = 2**30 (pos) or 2**30 - 1 (neg).
    nudge_lo = jnp.where(neg, jnp.uint32((1 << 30) - 1), jnp.uint32(1 << 30))
    hi, lo = u64_add(hi, lo, jnp.zeros_like(hi), nudge_lo)
    mag = (lo >> 31) | (hi << 1)  # (hi:lo) >> 31, low 32 bits
    result = jnp.where(neg, jnp.int32(0) - _i32(mag), _i32(mag))
    return jnp.where(overflow, jnp.int32(INT32_MAX), result)


def rounding_divide_by_pot(x, exponent) -> jax.Array:
    """gemmlowp RoundingDivideByPOT: rounding arithmetic shift right."""
    x = _i32(x)
    if isinstance(exponent, int):
        if exponent == 0:
            return x
        assert 0 < exponent < 32, exponent
        mask = jnp.int32((1 << exponent) - 1)
        remainder = x & mask
        threshold = (mask >> 1) + jnp.where(x < 0, jnp.int32(1), jnp.int32(0))
        return (x >> exponent) + (remainder > threshold).astype(jnp.int32)
    exponent = _i32(exponent)
    mask = ((jnp.int32(1) << exponent) - 1).astype(jnp.int32)
    remainder = x & mask
    threshold = (mask >> 1) + jnp.where(x < 0, jnp.int32(1), jnp.int32(0))
    shifted = jnp.where(exponent > 0, x >> jnp.maximum(exponent, 0), x)
    inc = jnp.logical_and(exponent > 0, remainder > threshold)
    return shifted + inc.astype(jnp.int32)


def saturating_left_shift(x, n) -> jax.Array:
    """x << n with int32 saturation (n: static int or traced int32 >= 0)."""
    x = _i32(x)
    if isinstance(n, int):
        if n == 0:
            return x
        assert 0 < n < 32
    shifted = x << n
    bad = (shifted >> n) != x
    sat = jnp.where(x >= 0, jnp.int32(INT32_MAX), jnp.int32(INT32_MIN))
    return jnp.where(bad, sat, shifted)


def saturating_add_i32(a, b) -> jax.Array:
    a = _i32(a)
    b = _i32(b)
    s = a + b  # wraps
    overflow_pos = jnp.logical_and(jnp.logical_and(a > 0, b > 0), s < 0)
    overflow_neg = jnp.logical_and(jnp.logical_and(a < 0, b < 0), s >= 0)
    s = jnp.where(overflow_pos, jnp.int32(INT32_MAX), s)
    return jnp.where(overflow_neg, jnp.int32(INT32_MIN), s)


def saturate_i16(x) -> jax.Array:
    return jnp.clip(_i32(x), INT16_MIN, INT16_MAX).astype(jnp.int16)


def saturate_i8(x) -> jax.Array:
    return jnp.clip(_i32(x), -128, 127).astype(jnp.int8)


def rounding_half_sum(a, b) -> jax.Array:
    """Exact (a + b + 1) >> 1 without 64-bit arithmetic (gemmlowp)."""
    a = _i32(a)
    b = _i32(b)
    return (a >> 1) + (b >> 1) + (((a & 1) + (b & 1) + 1) >> 1)


# ---------------------------------------------------------------------------
# Static (python-side) multiplier computation -- runs offline at calibration
# time, mirroring TFLite's QuantizeMultiplier.
# ---------------------------------------------------------------------------


def quantize_multiplier(real_multiplier: float) -> Tuple[int, int]:
    """Decompose real == m0/2**31 * 2**shift with m0 in [2**30, 2**31)."""
    if real_multiplier == 0.0:
        return 0, 0
    if real_multiplier < 0:
        raise ValueError("multipliers must be non-negative")
    mant, exp = math.frexp(real_multiplier)  # mant in [0.5, 1)
    m0 = int(round(mant * (1 << 31)))
    if m0 == (1 << 31):
        m0 //= 2
        exp += 1
    if exp > 31:
        raise ValueError(f"multiplier {real_multiplier} too large")
    if exp < -31:
        return 0, 0  # underflows to zero
    return m0, exp


def multiply_by_quantized_multiplier(x, m0, shift) -> jax.Array:
    """TFLite MultiplyByQuantizedMultiplier: rescale int32 by m0/2**31 * 2**shift.

    ``m0``/``shift`` may be python ints (static) or int32 arrays (per-channel).
    """
    x = _i32(x)
    if isinstance(shift, int):
        left = max(shift, 0)
        right = max(-shift, 0)
        y = saturating_rounding_doubling_high_mul(
            saturating_left_shift(x, left) if left else x, jnp.int32(m0)
        )
        return rounding_divide_by_pot(y, right)
    shift = _i32(shift)
    m0 = _i32(m0)
    left = jnp.maximum(shift, 0)
    right = jnp.maximum(-shift, 0)
    y = saturating_rounding_doubling_high_mul(saturating_left_shift(x, left), m0)
    return rounding_divide_by_pot(y, right)


# ---------------------------------------------------------------------------
# Integer transcendentals (gemmlowp fixedpoint.h ports)
# ---------------------------------------------------------------------------

_EXP_CONSTANT_TERM = 1895147668  # exp(-1/8) in Q0.31
_EXP_ONE_THIRD = 715827883  # 1/3 in Q0.31
# (exponent, exp(-2**exponent) in Q0.31)
_EXP_BARREL = (
    (-2, 1672461947),
    (-1, 1302514674),
    (0, 790015084),
    (1, 290630308),
    (2, 39332535),
    (3, 720401),
    (4, 242),
)
_ONE_Q31 = INT32_MAX  # gemmlowp's F0::One()
_K48_OVER_17 = 1515870810  # 48/17 in Q2.29
_K_NEG32_OVER_17 = -1010580540  # -32/17 in Q2.29
_INV_SQRT2_Q31 = 1518500250  # 2**-0.5 in Q0.31


def exp_on_interval_between_negative_one_quarter_and_0_excl(a) -> jax.Array:
    """exp(a) for a in (-1/4, 0]; a and result are Q0.31 (gemmlowp Taylor)."""
    a = _i32(a)
    srdhm = saturating_rounding_doubling_high_mul
    x = a + jnp.int32(1 << 28)  # t = a + 1/8, |t| <= 1/8
    x2 = srdhm(x, x)
    x3 = srdhm(x2, x)
    x4 = srdhm(x2, x2)
    x4_over_4 = rounding_divide_by_pot(x4, 2)
    # t^2/2 + t^3/6 + t^4/24 == (((t^4/4 + t^3) / 3) + t^2) / 2
    tmp = rounding_divide_by_pot(
        srdhm(x4_over_4 + x3, jnp.int32(_EXP_ONE_THIRD)) + x2, 1
    )
    ct = jnp.int32(_EXP_CONSTANT_TERM)
    return ct + srdhm(ct, x + tmp)


def exp_on_negative_values(a, integer_bits: int) -> jax.Array:
    """exp(a) for a <= 0 in Q_{m}.{31-m} (m = integer_bits); result Q0.31."""
    assert 0 <= integer_bits <= 29
    a = _i32(a)
    frac_bits = 31 - integer_bits
    one_quarter = jnp.int32(1 << (frac_bits - 2))
    mask = one_quarter - 1
    a_mod = (a & mask) - one_quarter  # in (-1/4, 0] of the input format
    result = exp_on_interval_between_negative_one_quarter_and_0_excl(
        a_mod << integer_bits  # exact rescale to Q0.31
    )
    remainder = a_mod - a  # >= 0: the "quarters" part of |a|
    srdhm = saturating_rounding_doubling_high_mul
    for exponent, multiplier in _EXP_BARREL:
        if integer_bits > exponent:
            shift_amount = frac_bits + exponent
            if 0 <= shift_amount < 31:
                bit = jnp.int32(1 << shift_amount)
                result = jnp.where(
                    (remainder & bit) != 0,
                    srdhm(result, jnp.int32(multiplier)),
                    result,
                )
    if integer_bits > 5:
        clamp_bound = jnp.int32(-(1 << (frac_bits + 5)))
        result = jnp.where(a < clamp_bound, jnp.int32(0), result)
    return jnp.where(a == 0, jnp.int32(_ONE_Q31), result)


def one_over_one_plus_x(a) -> jax.Array:
    """1/(1+a) for a in [0, 1] given as Q0.31; result in Q2.29.

    gemmlowp one_over_one_plus_x_for_x_in_0_1: 3 Newton-Raphson iterations for
    1/d around d = (1+a)/2 in [0.5, 1], seeded with 48/17 - 32/17*d.
    """
    a = _i32(a)
    srdhm = saturating_rounding_doubling_high_mul
    half_denominator = rounding_half_sum(a, jnp.int32(_ONE_Q31))
    x = jnp.int32(_K48_OVER_17) + srdhm(half_denominator, jnp.int32(_K_NEG32_OVER_17))
    one_q2_29 = jnp.int32(1 << 29)
    for _ in range(3):
        hdx = srdhm(half_denominator, x)  # Q0.31*Q2.29 -> Q2.29 of d*x
        one_minus_hdx = one_q2_29 - hdx
        x = x + saturating_left_shift(srdhm(x, one_minus_hdx), 2)
    # x ~= 1/d = 2/(1+a) in Q2.29; return 1/(1+a) = x/2 (exact shift).
    return x >> 1


def tanh_fp(a, integer_bits: int) -> jax.Array:
    """tanh of Q_{m}.{31-m} int32 -> Q0.31 int32 (gemmlowp)."""
    a = _i32(a)
    srdhm = saturating_rounding_doubling_high_mul
    neg = a < 0
    abs_a = jnp.where(neg, jnp.where(a == INT32_MIN, jnp.int32(INT32_MAX), -a), a)
    # t = exp(-2|a|).  Doubling a Q_{m} value == reinterpreting its raw bits
    # in Q_{m+1}: exact, saturation-free (gemmlowp does the equivalent).
    t = exp_on_negative_values(-abs_a, integer_bits + 1)
    one_minus_t = jnp.int32(_ONE_Q31) - t
    inv = one_over_one_plus_x(t)  # Q2.29 of 1/(1+t), in [0.5, 1]
    result = saturating_left_shift(srdhm(one_minus_t, inv), 2)  # back to Q0.31
    return jnp.where(neg, -result, result)


def sigmoid_fp(a, integer_bits: int) -> jax.Array:
    """logistic of Q_{m}.{31-m} int32 -> Q0.31 int32 (gemmlowp)."""
    a = _i32(a)
    srdhm = saturating_rounding_doubling_high_mul
    neg = a < 0
    abs_neg = jnp.where(neg, a, -a)  # -|a| <= 0
    t = exp_on_negative_values(abs_neg, integer_bits)
    # sigmoid(-|a|) = t / (1 + t)
    sig_neg = saturating_left_shift(srdhm(t, one_over_one_plus_x(t)), 2)
    result = jnp.where(neg, sig_neg, jnp.int32(_ONE_Q31) - sig_neg)
    return jnp.where(a == 0, jnp.int32(1 << 30), result)


# --- int16 wrappers: the LSTM-facing API (paper sec 3.2.1, TFLite semantics).


def tanh_q15(x, input_integer_bits: int = 3) -> jax.Array:
    """tanh: int16 Q_{m.15-m} in -> int16 Q0.15 out."""
    x32 = jnp.asarray(x).astype(jnp.int32) << 16  # Q_{m.15-m} -> Q_{m.31-m}
    y = tanh_fp(x32, input_integer_bits)
    return saturate_i16(rounding_divide_by_pot(y, 16))


def sigmoid_q15(x, input_integer_bits: int = 3) -> jax.Array:
    """sigmoid: int16 Q_{m.15-m} in -> int16 Q0.15 out."""
    x32 = jnp.asarray(x).astype(jnp.int32) << 16
    y = sigmoid_fp(x32, input_integer_bits)
    return saturate_i16(rounding_divide_by_pot(y, 16))


# ---------------------------------------------------------------------------
# Integer reciprocal square root / reciprocal (for LayerNorm, RMSNorm, softmax)
# ---------------------------------------------------------------------------


def integer_rsqrt_normalized(m_q31) -> jax.Array:
    """rsqrt of a mantissa in [0.5, 1) given as Q0.31; result Q2.29.

    Newton-Raphson: y <- y * (3 - m*y^2) / 2, four iterations from a linear
    seed; result in (1, sqrt(2)].
    """
    m = _i32(m_q31)
    srdhm = saturating_rounding_doubling_high_mul
    # seed: y0 ~= 1.7880 - 0.8047*m (linear fit; worst-case rel err ~3%)
    k_a = jnp.int32(int(round(1.7880 * (1 << 29))))  # Q2.29
    k_b = jnp.int32(int(round(0.8047 * (1 << 29))))  # Q2.29 coefficient
    # srdhm(Q0.31 m, Q2.29 k_b) = m*0.8047 * 2**29 -> Q2.29.
    y = k_a - srdhm(m, k_b)
    three_q27 = jnp.int32(3 << 27)
    for _ in range(4):
        y2 = srdhm(y, y)  # value y^2 * 2**27
        my2 = srdhm(m, y2)  # value m*y^2 * 2**27
        diff = three_q27 - my2  # (3 - m*y^2) * 2**27
        # y*(diff)/2: srdhm -> y*diff * 2**(29+27-31) = *2**25; want *2**28.
        y = saturating_left_shift(srdhm(y, diff), 3)
    return y


def integer_rsqrt_multiplier(hi, lo, extra_pow2: int = 0) -> Tuple[jax.Array, jax.Array]:
    """(m0, shift) int32 arrays with rsqrt(V)*2**extra_pow2 == m0/2**31 * 2**shift.

    V = hi*2**32 + lo (uint32 limbs, V > 0).  Feed the result to
    ``multiply_by_quantized_multiplier`` for per-row integer normalization.
    """
    hi = _u32(hi)
    lo = _u32(lo)
    lz = u64_leading_zeros(hi, lo)  # int32 in [0, 64]
    e = jnp.int32(64) - lz  # V = m * 2**e, m in [0.5, 1)
    # Extract the top 32 bits of V << lz (MSB lands at bit 63).
    lzc = jnp.clip(lz, 0, 63)
    lz_lt32 = lzc < 32
    sh = jnp.where(lz_lt32, lzc, lzc - 32).astype(jnp.uint32)
    # where(), not maximum(): an unsigned max (arith.maxui) has no Mosaic
    # lowering, and this runs inside the persistent TPU kernel
    sh_pos = sh > 0
    lo_part = jnp.where(
        sh_pos, lo >> (jnp.uint32(32) - jnp.where(sh_pos, sh, 1)),
        jnp.uint32(0)
    )
    top_lt = (hi << sh) | lo_part
    top_ge = lo << sh
    top = jnp.where(lz_lt32, top_lt, top_ge)  # in [2**31, 2**32)
    m_q31 = _i32(top >> 1)  # Q0.31 mantissa in [0.5, 1)
    y = integer_rsqrt_normalized(m_q31)  # Q2.29 in (1, sqrt(2)]
    # rsqrt(V) = rsqrt(m) * 2**(-e/2).  For odd e use an extra 1/sqrt(2):
    # 2**(-e/2) = 2**(-(e-1)/2) * 2**(-1/2); half_e = floor(e/2) either way.
    e_is_odd = (e & 1) != 0
    y = jnp.where(
        e_is_odd,
        saturating_rounding_doubling_high_mul(y, jnp.int32(_INV_SQRT2_Q31)),
        y,
    )
    half_e = e >> 1
    # value(y) = y_raw * 2**-29 = (y_raw / 2**31) * 2**2
    m0 = y
    shift = jnp.int32(2 + extra_pow2) - half_e
    return m0, shift.astype(jnp.int32)


def integer_recip_multiplier(x_i32, extra_pow2: int = 0) -> Tuple[jax.Array, jax.Array]:
    """(m0, shift) with (1/x)*2**extra_pow2 ~= m0/2**31 * 2**shift; x > 0 int32."""
    x = _i32(x_i32)
    lz = clz32(x)
    e = jnp.int32(32) - lz  # x = m * 2**e, m in [0.5, 1)
    m_q31 = x << jnp.maximum(lz - 1, 0)  # exact: MSB to bit 30
    # 1/m = 2/(1 + a) with a = 2m - 1 in [0, 1)
    a = (m_q31 - jnp.int32(1 << 30)) << 1
    inv = one_over_one_plus_x(a)  # Q2.29 of 1/(2m) in (0.5, 1]
    # 1/x = 2 * (1/(2m)) * 2**-e ; value(inv) = inv/2**31 * 2**2
    m0 = inv
    shift = jnp.int32(2 + 1 + extra_pow2) - e
    return m0, shift.astype(jnp.int32)
