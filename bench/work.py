"""Operations and bytes the served model needs, computed from shapes.

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


def decode_attention(context: int, rows: int, H: int, KH: int, hd: int,
                     d_out: int, P: int, R: int) -> Work:
    """One fused decode-attention call with its paired out-projection.

    ``context``: keys attended, summed over the ``rows`` slots (the live
    positions only).  Attention reads each live key and value once; the
    out-projection is a paired GEMM over the H*hd attended lanes with the
    sublayer residual fused.
    """
    attn = Work(4.0 * H * hd * context,
                (2 * KH * hd * context + rows * H * hd) * ACT_BYTES)
    proj = paired_gemm(rows, d_out, P, R, residual=True)
    # the attended values never leave the kernel: no write and reread of them
    return attn + Work(proj.flops, proj.bytes - rows * H * hd * ACT_BYTES)


def dense_flops_per_position(L: int, d: int, H: int, KH: int, hd: int, f: int) -> float:
    """Matmul operations of one token position through the decoder stack,
    as a dense model computes them (pairing changes how, not how much)."""
    per_layer = 2.0 * (d * H * hd + 2 * d * KH * hd + H * hd * d + 3 * d * f)
    return L * per_layer


def attention_flops(L: int, H: int, hd: int, context: int) -> float:
    """Score and value products over ``context`` attended keys, all layers."""
    return 4.0 * L * H * hd * context


def head_flops(d: int, V: int) -> float:
    """The tied output head for one position."""
    return 2.0 * d * V
