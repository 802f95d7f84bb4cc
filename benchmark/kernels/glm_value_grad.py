"""What one call of the resident fused GLM value-and-gradient kernel
(``ops/pallas_fused.py::fused_glm_value_grad``) MUST move and compute on one
chip, from shapes alone: the bf16 design matrix with its intercept column
read once, the f32 labels read once, beta and the (d + 1) gradient. What the
kernel moves beyond that (a 128x-padded (n, 1) ``y``, PERF.md) is waste, and
shows as a low share."""


def cost(rows_per_chip, d, params):
    n, w = int(rows_per_chip), int(d) + 1
    return {"bytes": n * w * 2 + n * 4 + 2 * w * 4,
            "flops": 4 * n * w}       # eta = X b, then g = X^T r
