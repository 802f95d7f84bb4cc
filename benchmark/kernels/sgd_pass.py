"""What ANY implementation of one ``partial_fit`` pass of a linear model
must move and compute on one chip, from shapes alone: every row's design
read once at the stated fit dtype's width (bfloat16: 2 bytes an entry) and
its float32 label read once; ``eta = X w`` and ``g = X^T r`` are 2 FLOP an
entry each. It reads the same work whether a pass builds a grid first, reads
every block twice or gathers blocks one at a time — all of that is waste and
shows as a low share — so the share built on it cannot pass 100 %."""


def cost(rows_per_chip, d, params):
    n, d = int(rows_per_chip), int(d)
    return {"bytes": n * d * 2 + n * 4, "flops": 4 * n * d}
