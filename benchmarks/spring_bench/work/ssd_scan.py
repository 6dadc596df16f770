"""Operations and bytes of one forward ``ssd_scan`` kernel call.

The kernel walks a grid of (batch, head, chunk) with chunk length L.
Per grid step, with state N and head dim P (see the kernel's own
docstring for the four products):

    scores  = C (L, N) @ B.T (N, L)      2 L L N
    y_intra = (scores * decay) @ x       2 L L P
    y_inter = C (L, N) @ h (N, P)        2 L N P
    h'      = (B * decay).T @ x          2 N L P

so flops = batch * heads * (S / L) * (2 L^2 N + 2 L^2 P + 4 L N P).

Bytes per grid step: x (L, P) at ``x_bytes``, the decay column and two
rows (3 L float32), B.T (N, L) and C (L, N) at ``bc_bytes`` (re-read per
head, as the index map does), and y (L, P) written at ``x_bytes``.
"""


def cost(batch: int, seq: int, heads: int, head_dim: int, d_state: int,
         chunk: int = 128, x_bytes: int = 4, bc_bytes: int = 4) -> tuple:
    steps = batch * heads * (-(-seq // chunk))
    l, n, p = chunk, d_state, head_dim
    flops = steps * (2.0 * l * l * n + 2.0 * l * l * p + 4.0 * l * n * p)
    nbytes = steps * float(2 * l * p * x_bytes + 3 * l * 4 + 2 * n * l * bc_bytes)
    return flops, nbytes
