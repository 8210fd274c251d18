"""Operations and least bytes of one ``_swiglu_kernel`` call
(kernels/w8a8_matmul.py: w8a8_swiglu_matmul_p), and how to tell its calls in
a trace.

Shapes: x (M, K) int8, w (K, N) int8 with N = 2P (gate | up), per-row and
per-column scales, per-(row, 128-column block) intervals; outputs y (M, N)
in f32 or bf16, hsw_q (M, P) int8 and three (M, 1) f32 row statistics.  The
least bytes read each operand and write each output once; the operations
are the matmul's 2 M K N (the epilogue's elementwise work is not counted).

The kernel has no name in the trace, so its calls are told by their
signature (trace_reduce.compact): results (T[M,N], s8[M,N/2], f32[M,1] x 3)
from operands (s8[M,K], s8[K,N], ...), a shape no other kernel has.
"""
import re

_SIG = re.compile(
    r"^\((f32|bf16)\[(\d+),(\d+)\], s8\[(\d+),(\d+)\], f32\[\d+,1\], "
    r"f32\[\d+,1\], f32\[\d+,1\]\) custom-call\(s8\[(\d+),(\d+)\] [^,]*, "
    r"s8\[(\d+),(\d+)\]")


def match(sig: str):
    """{"M", "K", "N", "out_bytes"} of a custom-call signature, or None."""
    m = _SIG.match(sig)
    if not m:
        return None
    dt, M, N, M2, P, Mx, K, K2, N2 = m.groups()
    M, N, M2, P, Mx, K, K2, N2 = map(int, (M, N, M2, P, Mx, K, K2, N2))
    if not (M == M2 == Mx and 2 * P == N and K == K2 and N == N2):
        return None
    return {"M": M, "K": K, "N": N, "out_bytes": 4 if dt == "f32" else 2}


def flops(M: int, K: int, N: int) -> float:
    return 2.0 * M * K * N


def bytes(M: int, K: int, N: int, out_bytes: int = 2) -> float:
    P = N // 2
    read = M * K + K * N + 4 * (2 * M + 2 * N + 2 * M * (N // 128))
    write = out_bytes * M * N + M * P + 3 * 4 * M
    return float(read + write)
