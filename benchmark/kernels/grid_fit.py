"""What ANY implementation of ONE objective evaluation of the fold-stacked
C grid must move and compute on one chip, from shapes alone: this chip's
design read once at the stated design precision (``design_itemsize`` bytes
an entry: 2, bfloat16) and, for every one of the ``n_models`` (fold, C)
models, ``eta = X b`` and ``g = X^T r`` — ``4 n d`` FLOP a model, every row
counted for every model (a model's held-out rows still pass through the
product before their weight drops them). At 4,194,304 x 256 and 50 models:
2.147 GB, 2.62 ms at the v5e's 819 GB/s; 2.15e11 FLOP, 1.09 ms at its bf16
peak: the read binds. Labels, fold ids and the (50, n) values are left out:
it stays a floor. A second read of X (the gradient product's), an f32
cotangent written and read back: all of that is the implementation's, shows
as a low share, and the share built on this cannot pass 100 %."""


def cost(rows_per_chip, d, params):
    n, d = int(rows_per_chip), int(d)
    return {"bytes": n * d * int(params["design_itemsize"]),
            "flops": 4 * n * d * int(params["n_models"])}
