"""Operations and bytes of one ``masked_matmul`` kernel call.

The three calls of one ``y = x @ w`` with x (M, K) and w (K, N):

    forward  x (M, K) @ w (K, N)    -> (M, N)
    dx       g (M, N) @ w.T (N, K)  -> (M, K)
    dw       x.T (K, M) @ g (M, N)  -> (K, N)

Each is counted as the logical dense product of its own (rows, inner,
cols), at the operands' stored dtypes, whatever tiles the kernel skips:

    flops = 2 * rows * inner * cols
    bytes = rows * inner * a_bytes + inner * cols * b_bytes
            + rows * cols * out_bytes

The output is the kernel's float32 accumulator, written once.
"""


def cost(rows: int, inner: int, cols: int, a_bytes: int = 4, b_bytes: int = 4,
         out_bytes: int = 4) -> tuple:
    flops = 2.0 * rows * inner * cols
    nbytes = float(rows * inner * a_bytes + inner * cols * b_bytes
                   + rows * cols * out_bytes)
    return flops, nbytes


def calls_of(m: int, k: int, n: int, *, forward: int = 1, backward: bool = True) -> list:
    """(rows, inner, cols) of every kernel call that one ``x @ w`` with x
    (M, K), w (K, N) makes in a step: ``forward`` forward calls (2 where
    the layer is recomputed in the backward pass), then dx and dw."""
    out = [(m, k, n)] * forward
    if backward:
        out += [(m, n, k), (k, m, n)]
    return out
