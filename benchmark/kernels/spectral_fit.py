"""What ANY Nyström spectral fit must move and compute on one chip, from
shapes alone: this chip's float32 X read once (4 n d bytes) and the cross
term of the affinity to the c landmarks, 2 n d c FLOP, counted at ONE bf16
pass of the MXU (the program asks for ``HIGHEST``, six passes: a choice of
precision, not a floor). A floor whatever implements the fit — the degrees,
G, the tall factorisation and the restarts on the (n, k) table all come on
top — so the share built on it reads the same work whatever carries it, and
cannot pass 100 %."""


def cost(rows_per_chip, d, params):
    n, c = int(rows_per_chip), int(params["n_components"])
    return {"bytes": n * int(d) * 4, "flops": 2 * n * int(d) * c}
