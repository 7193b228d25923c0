"""Operations and bytes of one ``dual_cd_block.fused_cd_pass`` call on the
matrix-free path (rbf), counted from the algorithm, not from the
kernel's padding or its re-reads.

One pass over a level of ``K`` partitions of ``m`` rows and ``d``
features rebuilds every Gram entry of every partition once and
multiplies it into the pass's step: per entry the cross term
``x_i . x_j`` (2d), the squared distance and the scaled exponent from
the row norms (3), the exponential (1) and the multiply-add of the
matvec (2). The in-tile greedy sweeps are serial and not counted.

Bytes: the diagonal Gram tiles the sweeps read (``K * ceil(m/B)`` tiles
of ``B x B``), and per row its features, norm, label and mask, its two
duals and its ``u`` read, its two duals and its product written, once.
"""
from __future__ import annotations

# the pallas call is named after the enclosing while body today ("%body.7"),
# so its output signature, (K, nblk, 2, B) duals and (K, nblk, B) u_d,
# identifies it; a stable kernel name is an open question (PERF.md)
PATTERN = r"^%(fused_cd_pass|body)(\.\d+)? = \(f32\[\d+,\d+,2,\d+\]"


def cost(K: int, m: int, d: int, B: int = 256, itemsize: int = 4):
    """(flops, bytes) of one pass."""
    flops = K * m * m * (2 * d + 6)
    nblk = -(-m // B)
    nbytes = (K * nblk * B * B + K * m * (d + 9)) * itemsize
    return float(flops), float(nbytes)
