"""What ANY implementation of ONE local Newton step of consensus ADMM must
move and compute on one chip, from shapes alone: this chip's design read
once at the stated design precision (``main_kernel.design_itemsize`` bytes
an entry: 4, float32), and the weighted Gram ``X^T W X`` —
``n d (d + 1)`` FLOP, the symmetric half of the ``2 n d^2`` product counted
once (a multiply and an add an entry of the upper triangle). The labels'
bytes, eta and the residual product (``4 n d`` FLOP) are left out: it stays
a floor. A second or third read of a block, a weighted copy written and read
back, both triangles of the Gram: all of that is the implementation's, shows
as a low share, and the share built on this cannot pass 100 %."""


def cost(rows_per_chip, d, params):
    n, d = int(rows_per_chip), int(d)
    return {"bytes": n * d * int(params["design_itemsize"]),
            "flops": n * d * (d + 1)}
