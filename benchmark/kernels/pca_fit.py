"""What ANY fit of a PCA must move on one chip, from shapes alone: this
chip's float32 X read once. A floor for every algorithm — the one-pass Gram
route reads X once, Halko's with two power iterations six times plus its
tall QRs — so the share built on it reads the same work whatever implements
the fit, and cannot pass 100 %. No FLOP floor is claimed (an exact method
needs 2 n d^2, a sketch 2 n d l; neither binds beside the read)."""


def cost(rows_per_chip, d, params):
    return {"bytes": int(rows_per_chip) * int(d) * 4, "flops": 0}
