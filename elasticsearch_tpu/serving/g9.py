"""`"%.9g" % float(x)` for a whole float32 vector, as numpy passes over the
vector: no Python call an element.

The packed lane's raw render (executor.response_raw) prints every score of
a batch with it. Why not `np.char.mod("%.9g", x)` (the lane's renderer up to
PR 29): that is numpy's `_vec_string`, one `str.__mod__` call an element
under the interpreter lock, 184 ms for the 256,000 scores of a top-1000
`_msearch` where the device program takes 60-150 ms.

`%.9g` prints 9 significant digits, correctly rounded (ties to even), in
positional form when the decimal exponent e of the ROUNDED value is in
-4 .. 8, with trailing zeros (and a bare point) removed. A float32 x has a
24-bit significand and 10^(8-e) is exact in float64 for e >= -4 (5^12 <
2^28), so x * 10^(8-e) is exact in float64 (24 + 28 bits) and `rint` of it
IS the correctly rounded 9-digit significand. Rows outside that (an
exponent form, inf, nan) take the scalar `%` and are counted as patched.
"""

from __future__ import annotations

import numpy as np

G9_WIDTH = 15                  # "-0.000" + 9 digits: the longest positional
_E_MIN, _E_MAX = -4, 8
_N_EXP = _E_MAX - _E_MIN + 1
_POW10 = 10.0 ** np.arange(_E_MIN, _E_MAX + 2)          # 1e-4 .. 1e9
_SCALE = 10.0 ** (8 - np.arange(_E_MIN, _E_MAX + 1))    # exact: 1e12 .. 1e0
# four decimal digits as four ASCII bytes, viewed as one uint32 a number:
# one take of a 4-byte item writes four digits. `_QUADS_CUT` is the same
# with the group's trailing zeros as NUL ("0500" -> "05\0\0", "0000" -> NULs)
_QUADS_S4 = np.char.zfill(np.arange(10_000).astype("U4"), 4).astype("S4")
_QUADS = _QUADS_S4.view(np.uint32)
_QUADS_CUT = np.char.rstrip(_QUADS_S4, b"0").view(np.uint32)
_DOT, _MINUS, _ZERO = np.uint8(ord(".")), np.uint8(ord("-")), np.uint8(ord("0"))


def _place(dst: np.ndarray, digits: np.ndarray, e: int, neg: bool) -> None:
    """Write the positional text of the sign and exponent `e` into `dst`
    (zeroed uint8[n, G9_WIDTH]) from `digits` (uint8[n, 9], ASCII, NUL where
    a trailing zero was cut)."""
    if neg:
        dst[:, 0] = _MINUS
        dst = dst[:, 1:]
    if e >= 0:                           # d..d [. d..d]
        ni = e + 1
        # a cut that reaches into the integer part ("100") is no cut there
        np.maximum(digits[:, :ni], _ZERO, out=dst[:, :ni])
        if ni < 9:
            np.multiply(digits[:, ni] != 0, _DOT, out=dst[:, ni])
            dst[:, ni + 1:10] = digits[:, ni:]
    else:                                # 0.0..0d..d
        nz = -e - 1
        dst[:, 0] = _ZERO
        dst[:, 1] = _DOT
        dst[:, 2:2 + nz] = _ZERO
        dst[:, 2 + nz:11 + nz] = digits


def g9_text(x: np.ndarray, out: np.ndarray | None = None
            ) -> tuple[np.ndarray, int]:
    """float32[N] -> (uint8[N, G9_WIDTH], patched): row i is the ASCII text
    of `"%.9g" % float(x[i])`, NUL-padded on the right (`.view("S15")` of a
    contiguous result is the texts). `out`, if given, is a ZEROED
    uint8[N, G9_WIDTH] to write (a column range of the caller's matrix).
    `patched` counts the rows that took the scalar `%`."""
    x = np.ascontiguousarray(x, np.float32)
    n = x.shape[0]
    if out is None:
        out = np.zeros((n, G9_WIDTH), np.uint8)
    if not n:
        return out, 0
    ax = np.abs(x).astype(np.float64)
    # decimal exponent against the table of powers (an exact classing: no
    # float32 lies between a power of ten and its nearest float64); 0 and
    # everything under 1e-4 class as -1, everything from 1e9 (inf, nan) as 13
    ei = np.searchsorted(_POW10, ax, side="right").astype(np.int8) - 1
    zero = ax == 0.0
    vec = ((ei >= 0) & (ei < _N_EXP)) | zero
    ei[zero] = -_E_MIN                                   # "0": e = 0, m = 0
    np.clip(ei, 0, _N_EXP - 1, out=ei)
    m = np.rint(ax * _SCALE[ei])
    m[~vec] = 0.0
    m = m.astype(np.uint32)
    carry = m == 1_000_000_000                           # 9.999999996 -> 10
    if carry.any():
        m[carry] = 100_000_000
        ei[carry] += 1
        vec &= ei < _N_EXP                               # 1e9: exponent form
        np.clip(ei, 0, _N_EXP - 1, out=ei)
    # nine digits as d0 | d1..d4 | d5..d8: three takes of 4-byte items, the
    # last nonzero group with its trailing zeros cut, the groups after it NUL
    hi = m // np.uint32(10_000)
    lo = (m - hi * np.uint32(10_000)).astype(np.uint16)
    top = (hi // np.uint32(10_000)).astype(np.uint16)
    mid = (hi - top.astype(np.uint32) * np.uint32(10_000)).astype(np.uint16)
    quads = np.empty((n, 3), np.uint32)
    quads[:, 0] = _QUADS[top]
    quads[:, 1] = _QUADS[mid]
    quads[:, 2] = _QUADS_CUT[lo]
    lo0 = np.flatnonzero(lo == 0)
    quads[lo0, 1] = _QUADS_CUT[mid[lo0]]
    digits = quads.view(np.uint8)[:, 3:]                 # [n, 9]
    # where the point and the digits go is the sign's and the exponent's,
    # and a request's scores span a handful of exponents: the largest
    # group's columns are written for every row, the other groups' rows
    # then rewritten
    group = ei + np.int8(_N_EXP) * np.signbit(x)
    counts = np.bincount(group)
    order = np.argsort(-counts, kind="stable")
    _place(out, digits, *_group_of(order[0]))
    for g in order[1:]:
        if not counts[g]:
            break
        rows = np.flatnonzero(group == g)
        blk = np.zeros((rows.size, G9_WIDTH), np.uint8)
        _place(blk, digits[rows], *_group_of(g))
        out[rows] = blk
    odd = np.flatnonzero(~vec)
    for i in odd.tolist():               # exponent form, inf, nan
        t = ("%.9g" % float(x[i])).encode()
        out[i] = 0
        out[i, :len(t)] = np.frombuffer(t, np.uint8)
    return out, int(odd.size)


def _group_of(g) -> tuple[int, bool]:
    """group number -> (decimal exponent, negative?)"""
    return int(g) % _N_EXP + _E_MIN, int(g) >= _N_EXP
