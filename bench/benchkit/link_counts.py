"""Operations and bytes of the Macau link stage, per sweep and chip.

Counted at the stage's interface from the compound rows ``n_rows``, the
side information's width ``d`` and the latent width ``k``, whatever
implements the stage (FLOP count a multiply and an add as two):

* three products of the (n_rows, d) side with a (d, k) or (n_rows, k)
  operand: the centring ``F beta``, ``F^T U`` and the prior means
  ``F beta``: 3 x 2 n_rows d k;
* the (d, d) Cholesky factor of ``A = F^T F + lambda I``: d^3 / 3;
* three triangular solves with k right-hand sides (the mean's two, the
  noise's one): 3 d^2 k.

The static Gram ``F^T F`` is computed once, when the data is built, and
is not counted.  Bytes: the float32 side read twice (the centring and ``F^T U``
share a pass, the prior means need the new beta), the Gram read once,
the factor read and the per-row prior means written once.
"""
from __future__ import annotations

F32 = 4


def link_flops(n_rows: int, d: int, k: int) -> float:
    return 6.0 * n_rows * d * k + d ** 3 / 3.0 + 3.0 * d * d * k


def link_bytes(n_rows: int, d: int, k: int) -> float:
    side = 2.0 * n_rows * d * F32
    gram = float(d) * d * F32
    rows = 2.0 * n_rows * k * F32
    return side + gram + rows
