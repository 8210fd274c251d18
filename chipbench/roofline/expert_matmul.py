"""Operations and least bytes of one held-experts W8A8 call
(kernels/w8a8_matmul.py: w8a8_matmul_p, named ``w8a8_expert_matmul`` by
models/moe.py), and how to tell its calls in a trace.

The held experts of a layer run as two such calls: their gate and up
projections as one grouped matmul (K = hidden, N = 2 x experts held x the
expert width rounded up to 128 lanes), and their down projections stacked
along K (K = experts held x expert width, rounded up to 128; N = hidden).
Shapes: x (M, K) int8, w (K, N) int8, per-row and per-column scales and
column sums, four (M, 1) or (M, N/128) epilogue operands; output y (M, N)
in f32 or bf16.  The least bytes read each operand and write the output
once; the operations are the matmul's 2 M K N.

The kernel's name is not in a call's signature (trace_reduce.compact), so
its calls are told by their shapes: a single-output W8A8 call whose (K, N)
is one of the two the configuration's expert widths give.
"""
import re

_SIG = re.compile(
    r"^(f32|bf16)\[(\d+),(\d+)\] custom-call\(s8\[(\d+),(\d+)\] [^,]*, "
    r"s8\[(\d+),(\d+)\] [^,]*, f32\[\d+,1\] [^,]*, s32\[\d+,1\] [^,]*, "
    r"f32\[1,\d+\] [^,]*, s32\[1,\d+\] [^,]*, f32\[\d+,(\d+)\]")


def _up128(n: int) -> int:
    return n + (-n) % 128


def widths(config: dict) -> set:
    """The (K, N) of the gate/up and the down call of a configuration's
    held experts; empty for a configuration without routed experts."""
    if "moe_intermediate_size" not in config:
        return set()
    d = int(config["hidden_size"])
    E = int(config["n_routed_experts"])
    F = int(config["moe_intermediate_size"])
    return {(_up128(d), 2 * E * _up128(F)), (_up128(E * F), d)}


def match(sig: str, config: dict):
    """{"M", "K", "N", "E", "out_bytes"} of a custom-call signature that is one
    of the configuration's expert matmuls, or None."""
    m = _SIG.match(sig)
    if not m:
        return None
    dt, M, N, Mx, K, K2, N2, E = m.groups()
    M, N, Mx, K, K2, N2, E = map(int, (M, N, Mx, K, K2, N2, E))
    if not (M == Mx and K == K2 and N == N2 and E in (1, N // 128)):
        return None
    if (K, N) not in widths(config):
        return None
    return {"M": M, "K": K, "N": N, "E": E,
            "out_bytes": 4 if dt == "f32" else 2}


def flops(M: int, K: int, N: int) -> float:
    return 2.0 * M * K * N


def bytes(M: int, K: int, N: int, out_bytes: int = 2, E: int = 1) -> float:
    """``E`` epilogue columns: 1 (per row) or N / 128 (per 128-lane block)."""
    read = M * K + K * N + 4 * (2 * M + 2 * N + 4 * M * E)
    return float(read + out_bytes * M * N)
