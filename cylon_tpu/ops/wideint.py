"""64-bit two's-complement integers as a pair of uint32 words ``(hi, lo)``,
for a backend without x64 (the chip): add, subtract, multiply, a few
shifts, the range of a column and the way to a float32.

A plane-held column (``data/column.py``) is ONE ``uint32[2, n]`` array;
here a value is the pair of its planes, two arrays of one shape (or two
scalars: a pair broadcasts like its words). Every operation is exact
modulo 2^64, which is exact outright whenever the true result fits an
int64: whether it does is the CALLER's to show before it dispatches
(``ops/expr.py`` does, from observed ranges) - nothing here can raise on
the device. No operation needs a 64-bit lane: 32-bit adds with a carry
by an unsigned compare, 32 x 32 -> 64 products from four 16-bit ones.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32
MASK32 = 0xFFFFFFFF
INT32_RANGE = (-(1 << 31), (1 << 31) - 1)
INT64_RANGE = (-(1 << 63), (1 << 63) - 1)


def fits(lo: int, hi: int, rng) -> bool:
    return rng[0] <= lo and hi <= rng[1]


def _u32(x):
    return jax.lax.bitcast_convert_type(x, U32)


def _i32(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def const(value: int):
    """The pair of a Python integer (taken modulo 2^64)."""
    v = int(value) & ((1 << 64) - 1)
    return np.uint32(v >> 32), np.uint32(v & MASK32)


def from_int32(x):
    """An int32 array sign-extended to a pair."""
    return _u32(x >> 31), _u32(x)


def from_uint32(x):
    return jnp.zeros_like(x), x


def low_int32(a):
    """The value of a pair whose range fits an int32."""
    return _i32(a[1])


def add(a, b):
    lo = a[1] + b[1]
    return a[0] + b[0] + (lo < a[1]).astype(U32), lo


def sub(a, b):
    return a[0] - b[0] - (a[1] < b[1]).astype(U32), a[1] - b[1]


def neg(a):
    return sub(const(0), a)


def mul_u32(a, b):
    """The whole 64-bit product of two uint32 arrays."""
    a0, a1 = a & U32(0xFFFF), a >> 16
    b0, b1 = b & U32(0xFFFF), b >> 16
    p00, p01, p10, p11 = a0 * b0, a0 * b1, a1 * b0, a1 * b1
    mid = p01 + p10
    mid_carry = (mid < p01).astype(U32)      # worth 2^48
    lo = p00 + (mid << 16)
    hi = p11 + (mid >> 16) + (mid_carry << 16) + (lo < p00).astype(U32)
    return hi, lo


def mul_i32(a, b):
    """The whole 64-bit product of two int32 arrays."""
    ua, ub = _u32(a), _u32(b)
    hi, lo = mul_u32(ua, ub)
    zero = jnp.zeros_like(hi)
    return (hi - jnp.where(a < 0, ub, zero) - jnp.where(b < 0, ua, zero)), lo


def mul(a, b):
    """The low 64 bits of the product of two pairs."""
    hi, lo = mul_u32(a[1], b[1])
    return hi + a[0] * b[1] + a[1] * b[0], lo


def shr(a, k: int):
    """A pair shifted right (logically) by the static ``k`` in [0, 64)."""
    if k == 0:
        return a
    if k >= 32:
        return jnp.zeros_like(a[0]), a[0] >> (k - 32) if k > 32 else a[0]
    return a[0] >> k, (a[1] >> k) | (a[0] << (32 - k))


# -- integers of any number of words, most significant first (a pair is
# two): what the exact sum of many int64s takes before it is cut ----------


def wide_const(value: int, words: int):
    v = int(value) & ((1 << (32 * words)) - 1)
    return tuple(np.uint32((v >> (32 * (words - 1 - i))) & MASK32)
                 for i in range(words))


def wide_from_int32(x, words: int):
    return (_u32(x >> 31),) * (words - 1) + (_u32(x),)


def wide_add(a, b):
    out, carry = [], None
    for x, y in zip(reversed(a), reversed(b)):
        s = x + y
        c = (s < x).astype(U32)
        if carry is not None:
            t = s + carry
            c = c | (t < s).astype(U32)
            s = t
        out.append(s)
        carry = c
    return tuple(reversed(out))


def wide_neg(a):
    return wide_add(tuple(~w for w in a), wide_const(1, len(a)))


def wide_shl(a, k: int):
    """Shifted left by the static ``k`` in [0, 32 * words)."""
    q, r = divmod(k, 32)
    le = list(reversed(a))                  # least significant first
    zero = jnp.zeros_like(le[0])
    out = []
    for i in range(len(le)):
        cur = le[i - q] if i - q >= 0 else zero
        below = le[i - q - 1] if i - q - 1 >= 0 else zero
        out.append(cur if r == 0 else (cur << r) | (below >> (32 - r)))
    return tuple(reversed(out))


def fits_int64(a):
    """Whether a three-word integer (w2, w1, w0) is an int64's value: the
    top word is the sign extension of the next."""
    return a[0] == _u32(_i32(a[1]) >> 31)


def is_negative(a):
    return _i32(a[0]) < 0


def minmax(a):
    """uint32[4]: the least and the largest value of a pair of arrays, as
    (min hi, min lo, max hi, max lo). Two passes: the low word of the
    minimum is looked for among the rows that hold the least high word.
    (Bounds from independent reductions of the two planes would be one
    pass on paper; on a v5e the eight reductions it takes to keep them
    tight for small values of either sign ran 14.4 ms where these two
    passes run 7.7, over three columns of 7.5e7 rows: PERF.md section 6,
    PR 42.)"""
    hi_s = _i32(a[0])
    mn, mx = hi_s.min(), hi_s.max()
    return jnp.stack([
        _u32(mn), jnp.where(hi_s == mn, a[1], U32(MASK32)).min(),
        _u32(mx), jnp.where(hi_s == mx, a[1], U32(0)).max()])


def minmax_int32(x):
    """`minmax` of an int32 array, in the same four words."""
    mn, mx = from_int32(x.min()), from_int32(x.max())
    return jnp.stack([mn[0], mn[1], mx[0], mx[1]])


def range_of(words) -> tuple:
    """(lo, hi) as Python integers from `minmax`'s four words on the host."""
    w = [int(x) for x in words]

    def signed(hi, lo):
        v = (hi << 32) | lo
        return v - (1 << 64) if v >> 63 else v

    return signed(w[0], w[1]), signed(w[2], w[3])


def to_float32_pair(a):
    """(h, l): two float32 with h + l the pair's value to about 2^-46
    relative, |l| <= ulp(h) / 2 - the value for an arithmetic that keeps
    more than a float32's 24 bits (`divide_float32`). The magnitude goes
    as three exact chunks of 24, 24 and 16 bits."""
    negative = is_negative(a)
    m = jax.tree.map(lambda p, q: jnp.where(negative, p, q), neg(a), a)
    f32 = jnp.float32
    c0 = (m[1] & U32(0xFFFFFF)).astype(f32)
    c1 = (shr(m, 24)[1] & U32(0xFFFFFF)).astype(f32) * f32(2.0 ** 24)
    c2 = (m[0] >> 16).astype(f32) * f32(2.0 ** 48)
    h, e = two_sum(c2, c1)
    h, e2 = two_sum(h, c0)
    lo = e + e2
    h, lo = two_sum(h, lo)
    sign = jnp.where(negative, f32(-1), f32(1))
    return h * sign, lo * sign


def two_sum(a, b):
    """(s, e): s = fl(a + b) and a + b = s + e exactly (Knuth)."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _two_prod(a, b):
    """(p, e): p = fl(a * b) and a * b = p + e exactly (Dekker's split:
    no fused multiply-add is assumed)."""
    def split(x):
        t = x * jnp.float32(4097.0)
        h = t - (t - x)
        return h, x - h

    p = a * b
    ah, al = split(a)
    bh, bl = split(b)
    return p, ((ah * bh - p) + ah * bl + al * bh) + al * bl


def divide_float32(num, den):
    """The quotient of two float32 pairs (`to_float32_pair`) as ONE
    float32, within about one unit of 2^-24 relative: a first quotient,
    the exact residue of it, a correction. ``den`` is not zero."""
    q0 = num[0] / den[0]
    p, e = _two_prod(q0, den[0])
    r = ((num[0] - p) - e) + (num[1] - q0 * den[1])
    return q0 + r / den[0]
