"""serving/g9.py: `"%.9g" % float(x)` for a float32 vector, byte for byte."""

import numpy as np
import pytest

from elasticsearch_tpu.serving.g9 import G9_WIDTH, g9_text

F32 = np.finfo(np.float32)


def _texts(out: np.ndarray) -> list[str]:
    return [t.decode() for t in
            np.ascontiguousarray(out).view(f"S{G9_WIDTH}")[:, 0].tolist()]


def _across_exponents():
    rng = np.random.default_rng(30)
    mag = 10.0 ** rng.uniform(-8, 12, 20_000)
    return (mag * rng.choice([-1.0, 1.0], mag.size)).astype(np.float32)


def _powers_of_ten():
    p = (10.0 ** np.arange(-8, 13)).astype(np.float32)
    return np.concatenate([p, np.nextafter(p, np.float32(0)),
                           np.nextafter(p, np.float32(np.inf)), -p])


def _round_up_to_1e9():
    # the 9-digit significand rounds up to 10^9: one more digit before the
    # point, and from 999999999.5 on the exponent form
    return np.array([9.9999999996, 99.999999996, 0.99999999996,
                     0.00099999999996, 999999.9996, 99999999.96,
                     999999999.6, 9.9999999996e-5, 0.099999999996],
                    np.float32)


CASES = {
    "exponents -8 .. 12": _across_exponents,
    "powers of ten and their neighbours": _powers_of_ten,
    "significands that round up to 10^9": _round_up_to_1e9,
    "zero": lambda: np.array([0.0, -0.0], np.float32),
    "negatives": lambda: -np.abs(_across_exponents()[:2_000]),
    "smallest normal": lambda: np.array([F32.tiny, -F32.tiny], np.float32),
    "denormal": lambda: np.array(
        [1e-45, 7e-42, -3e-39, F32.tiny / 2], np.float32),
    "largest float32": lambda: np.array([F32.max, -F32.max], np.float32),
    "not finite": lambda: np.array([np.inf, -np.inf, np.nan], np.float32),
    "trailing zeros into the integer part": lambda: np.array(
        [100, 120, 1e8, 123456000, 10.5, 2.5, 0.5, 16777216, 1050.25, 7],
        np.float32),
    "one exponent, one sign": lambda: np.linspace(
        10, 99, 4_096, dtype=np.float32),
    "bm25-like: sorted rows over three exponents": lambda: np.sort(
        np.random.default_rng(3).gamma(2.0, 4.0, (64, 100))
        .astype(np.float32), axis=1)[:, ::-1].reshape(-1),
    "empty": lambda: np.zeros(0, np.float32),
}


@pytest.mark.parametrize("case", list(CASES))
def test_text_is_what_percent_9g_prints(case):
    x = CASES[case]()
    out, patched = g9_text(x)
    assert out.shape == (x.size, G9_WIDTH) and out.dtype == np.uint8
    want = ["%.9g" % float(v) for v in x]
    assert _texts(out) == want
    # nine significant digits bring a float32 back
    back = np.array([float(t) for t in want], np.float64).astype(np.float32)
    np.testing.assert_array_equal(back, x)
    # the vector pass takes every positional text but the denormal-small
    # and the huge: exactly the rows `%` prints with an exponent, and the
    # non-finite
    odd = sum("e" in t or "n" in t for t in want)
    assert patched == odd


def test_writes_into_a_column_range_of_the_callers_matrix():
    x = _across_exponents()[:500]
    m = np.full((x.size, 40), ord("#"), np.uint8)
    m[:, 7:7 + G9_WIDTH] = 0
    out, _ = g9_text(x, out=m[:, 7:7 + G9_WIDTH])
    assert np.shares_memory(out, m)
    assert _texts(m[:, 7:7 + G9_WIDTH]) == ["%.9g" % float(v) for v in x]
    assert (m[:, :7] == ord("#")).all() and (m[:, 22:] == ord("#")).all()
