"""Rounding to the lower precisions the controls compute in."""

from __future__ import annotations

import numpy as np

#: explicit mantissa bits (the leading one is implied)
MANTISSA_BITS = {"float32": 23, "bfloat16": 7, "fp8_e4m3": 3}


def round_to(x, precision: str) -> np.ndarray:
    """x rounded to the nearest value with that many mantissa bits (ties to
    even); the exponent range is left alone, so this is never harsher than
    the real type. float32 in, float32 out for bfloat16; float64 otherwise."""
    bits = MANTISSA_BITS[precision]
    if precision == "float32":
        return np.asarray(x, dtype=np.float32)
    x = np.asarray(x, dtype=np.float64)
    m, e = np.frexp(x)                      # x = m * 2**e, 0.5 <= |m| < 1
    scale = float(1 << (bits + 1))
    return np.ldexp(np.rint(m * scale) / scale, e)
