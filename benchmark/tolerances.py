"""The bands that decide ``correct``, each beside its reason. The first
block is a COPY of ``chip_smoke.py``'s (PR 21, measured on the v5e); the
rest are this benchmark's own. Kept here so no later PR can move them.
"""

# A bf16 design matrix rounds every x_ij (and the beta it multiplies) to 8
# mantissa bits, relative error <= 2^-8 per factor, with f32 accumulation.
# The x roundings are independent and average out over the rows of a sum;
# the beta rounding is the same for every row and does not: it moves the
# gradient by H @ d_beta whatever n is. So a gradient is judged against the
# problem's gradient SCALE — the largest entry of the reference gradient at
# beta = 0 on the same rows — never against the gradient at the point
# itself, which at a fitted coef_ is nearly zero while the rounding error is
# not. (PR 21 on the chip: loss within 2e-5 relative, gradient within 2.4e-3
# of the scale.) One precision rung lower (8-bit floats, 3 mantissa bits)
# is 32x these and fails both.
TOL_BF16_LOSS = 1e-3
TOL_BF16_GRAD = 1e-2
# predict's decision values are an f32 matvec, which XLA lowers on the v5e
# to f32 multiplies and a reduction (the trace shows multiply_reduce_fusion,
# no MXU pass): f32-exact, 1.2e-6 on the chip (my chip run, PR 22). A single
# bf16 pass would move eta by ~2^-9 |eta| (|eta| up to ~6) and a probability
# by a quarter of that, ~3e-3, and fails; three-pass bf16 (~1e-5) passes.
TOL_PROBA = 1e-3

# logreg, excess of the reference loss at coef_ over the reference's own
# optimum ON THE SAMPLE. The fit saw all n rows, the optimum only the m
# sample rows, so even an exact fit sits above the sample optimum by about
# d / (2 m) (Wilks: 2 m * excess ~ chi^2 with d + 1 degrees of freedom,
# standard deviation sqrt(2 d) / (2 m)). Band: that mean plus six standard
# deviations plus the stopping rule's own slack tol^2 / (2 mu) with
# mu ~ 0.05 the curvature — a fit that stopped with a gradient norm ten
# times the stated tol lands outside it.
def logreg_excess_band(d, m, tol):
    return (d + 1) / (2.0 * m) + 6.0 * (2.0 * (d + 1)) ** 0.5 / (2.0 * m) \
        + tol * tol / (2.0 * 0.05)


# logreg, stationarity on the check rows: the fit stops when ITS gradient
# (bf16 design, bf16-rounded beta) has 2-norm <= tol; the f32 reference
# gradient at the same point differs by the bf16 band above. Judged on the
# largest entry: <= tol + TOL_BF16_GRAD * scale. When the check rows are a
# sample of m, a sampling term is added: each entry of the sample gradient
# at the full-data optimum has standard deviation <= 0.5 / sqrt(m)
# (|y - p| <= 1, unit-variance features; 0.5 is the worst case p = 1/2),
# and the largest of d + 1 entries stays under 4.5 of those.
def logreg_grad_band(tol, scale, m, sampled):
    return tol + TOL_BF16_GRAD * scale + (4.5 * 0.5 / m ** 0.5 if sampled
                                          else 0.0)


# kmeans. The program's distances are ||x||^2 - 2 x.c + ||c||^2 with the
# cross term an f32 matmul at the TPU's default precision (bf16 multiplies,
# f32 sums): each of d products carries ~2^-8 relative error, so a squared
# distance is off by ~2 * 2^-8 * |x||c| / sqrt(d) * few — PR 21 measured
# 1.1e-2 relative on the Lloyd inertia (cancellation against the exact
# norms) and 2.6e-4 between kernel and XLA flavour.
# A label may differ from the reference's only where the two candidate
# centres are that close: |d2_ref[own] - d2_ref[ref]| <= TIE * d2_min.
# Centres at the noise's scale keep |x.c| far below ||x||^2, so the cross
# term's rounding is small against d2 ~ d: at 8,388,608 x 128 the chip's
# worst such gap was 3.8e-4 with 0.27-0.30 % of sample rows differing (my
# chip runs, PR 22, the PR's first shape); at the source's 256 features the
# same absolute error sits on twice the distance — a bf16-rounded cross term
# emulated on the CPU over 262,144 rows gives 2.1e-4 and 0.27 %. The bands
# are five and three and a half times those; distances from operands with
# three fewer mantissa bits (8x the error) land outside both.
TOL_KMEANS_TIE = 1e-3
TOL_KMEANS_MISMATCH_SHARE = 1e-2
# inertia_ (all rows) against the reference's on the sample, per row: the
# precision band above plus sampling noise (per-row d2 has relative spread
# sqrt(2/d) ~ 0.09 at d = 256; over m = 262,144 rows 2e-4, and the sample is
# the rows of the first chunk, whose mix of clusters differs a little from
# the whole): 1.6e-3 and 1.8e-4 on the chip at the first shape (my chip
# runs, PR 22).
TOL_KMEANS_INERTIA = 5e-3
# The final centres must have earned at least this share of the inertia
# improvement the reference's own Lloyd run (same init, same iteration
# count) earns on the sample. On few rows the reference overfits its sample
# (centre noise ~ d / rows-per-cluster: at d = 256 a 65,536-row sample puts
# the full-data centres at 0.62 of the reference's gain, a 262,144-row one
# at 1.06 — CPU, numpy Lloyd over all 4,194,304 rows), so the traffic's
# sample is the larger. A fit that stopped after half its iterations, or
# updated centres from wrong sums, earns under half.
KMEANS_MIN_GAIN_SHARE = 0.5
