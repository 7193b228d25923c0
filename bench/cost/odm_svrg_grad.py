"""Operations and bytes of one ``odm_grad.odm_svrg_grad`` call: the DSVRG
inner direction ``g_w - g_a + h`` over one minibatch of ``b`` rows and
``d`` features.

Per row: both margins ``y x.[w; a]`` (4d + 2), the two clipped hinge
coefficients and their masked difference (about 10), and the
back-projection ``x * dcoef`` accumulated (2d). Bytes: the rows, their
labels and mask, the three d-vectors in and the direction out.
"""
from __future__ import annotations

PATTERN = r"^%odm_svrg_grad(\.\d+)? = "


def cost(b: int, d: int, itemsize: int = 4):
    """(flops, bytes) of one call."""
    flops = b * (6 * d + 12)
    nbytes = (b * d + 2 * b + 4 * d) * itemsize
    return float(flops), float(nbytes)
