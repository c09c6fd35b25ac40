"""Operations and bytes the served model needs, computed from shapes: the
pieces that do not depend on the architecture.  Each architecture's
reference (``bench/references/<architecture>.py``) counts its own GEMM
shapes, positions, attention and head from its configuration file.

The counts are of the work the algorithm needs, not of what an
implementation happens to do: a kernel that streams padding, or rereads
what it could keep, shows as a lower roofline share, never as more work.
Bytes count each operand read once and each result written once, at the
served dtype (bfloat16, two bytes).
"""
from __future__ import annotations

import dataclasses

ACT_BYTES = 2  # bfloat16 activations, weights and cache entries


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: Work) -> Work:
        return Work(self.flops + other.flops, self.bytes + other.bytes)


def paired_gemm(M: int, N: int, P: int, R: int, *, residual: bool = False,
                bias: bool = False) -> Work:
    """One structured paired GEMM ``y = (x[I] - x[J]) K + x[R] W_R`` (+ epilogue).

    M rows, N columns, P pairs and R residual rows: P subtracts per row, a
    contraction over P + R lanes, and reads of x[I], x[J], x[R], K, W_R (and
    the bias and the fused residual), one write of y.
    """
    flops = 2.0 * M * N * (P + R) + M * P
    read = (M * (2 * P + R) + (P + R) * N + (M * N if residual else 0)) * ACT_BYTES
    read += N * 4 if bias else 0
    return Work(flops, read + M * N * ACT_BYTES)


def fused_out_projection(attn: Work, rows: int, K: int, N: int, P: int, R: int) -> Work:
    """One layer's attention (``attn``, from the architecture's reference)
    with its paired out-projection fused into the same kernel.

    The out-projection is a paired GEMM of ``rows`` rows over the K
    attended lanes into N columns, P pairs and R residual rows, with the
    sublayer residual fused; the attended values never leave the kernel, so
    their write and reread are not counted.
    """
    proj = paired_gemm(rows, N, P, R, residual=True)
    return attn + Work(proj.flops, proj.bytes - rows * K * ACT_BYTES)
