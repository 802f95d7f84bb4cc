"""What one call of the fused Lloyd statistics kernel
(``ops/pallas_fused.py::fused_lloyd_stats``) MUST move and compute on one
chip: f32 X read once, the (k, d) centres, sums and counts written; the
distance cross term and the one-hot sums, 2 n k d FLOP each."""


def cost(rows_per_chip, d, params):
    n, d, k = int(rows_per_chip), int(d), int(params["n_clusters"])
    return {"bytes": n * d * 4 + 2 * k * d * 4 + k * 4,
            "flops": 4 * n * k * d}
