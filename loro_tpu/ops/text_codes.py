"""Device code points -> ``str``: the one way the repo builds a text
from what a text kernel emits.

The kernels answer a document as an i32 row of Unicode code points and
a count.  numpy only, so ``parallel/`` and ``ops/`` may both import it.
"""
from __future__ import annotations

import numpy as np


def text_from_codes(codes_row, count) -> str:
    """``codes_row[:count]`` as a ``str``, by ONE bulk codec call — equal
    to joining ``chr`` of each code, for every row a kernel can emit:
    U+0000, astral code points and LONE surrogates included (Loro text
    is indexable by UTF-16 unit, and ``chr`` accepts them).
    Elements past ``count`` are never read.  A code outside
    ``range(0x110000)`` raises a ``ValueError`` (``UnicodeDecodeError``
    is one), as ``chr`` does: an answer is exact or it is an exception.
    """
    codes = np.asarray(codes_row)[: int(count)]
    units = codes.astype("<u4")
    # a row wider than 32 bits could wrap into range: refuse it as chr would
    if codes.dtype.itemsize > 4 and not np.array_equal(units, codes):
        raise ValueError("text code outside range(0x110000)")
    return units.tobytes().decode("utf-32-le", "surrogatepass")
