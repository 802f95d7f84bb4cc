"""The limits that decide ``correct`` in the cell ``logreg_admm_l1``
(``families/glm_admm.py::check_outputs``), each beside the TWO readings it
lies between — the largest the program gives over its seeds, and what the
reference gives when computed in bfloat16, the precision below the stated
float32 (the control: ``references/logreg_l1.py::admm(fault=
"bf16_design")``, the design and the coefficients rounded to bfloat16 in
every product, as one bf16 pass of the MXU rounds both operands) — and the
reason it may sit there. "Chip" readings are runs of ``benchmark/run.py`` /
``benchmark/tools/admm_faults.py`` on one TPU v5e at the cell's 4,194,304 x
256 rows (my chip runs, PR 36); "CPU" readings are correctness facts at
small sizes, never speeds. ``tolerances.py`` is the accepted cells' and is
not edited; ``TOL_PROBA`` is read from it.
"""

# --- the stop ---------------------------------------------------------------
# A fit must have stopped before ``max_iter`` with primal <= tol and dual <=
# tol: the program's own numbers (``solver_info_``), held to the stated rule.
# And its count of local Newton steps must be one the stated local rule can
# give: a solve ends after the step whose Newton decrement fell to tol**2,
# at most ``local_iter`` steps; the first solve starts cold (from zero,
# decrement ~1e-2), so it cannot end on its first step, and
#   n_iter < local_steps <= local_iter x n_iter.
#   chip, the program: 37 steps in 16 iterations, every fit of every run
#   chip, the reference's ADMM run rightly (admm_faults.py): 59 in 38
#   a run of ONE step a solve reads local_steps == n_iter (38 in 38) and
#   fails here — and nowhere else: inexact consensus ADMM heals itself, the
#   fault changes the path of the iterates and not their fixed point (KKT
#   4.6e-6 and excess 5e-9 beside the right run's 4.4e-6 and 2.6e-8)

# --- stationarity: the reference's KKT residual at (coef_, intercept_) ------
# What any sound ADMM stopped at tol meets (the CEILING, not the limit): with
# exact local solves the iterates satisfy, after outer iteration k,
#   grad f(b_k) + rho u_k = -rho (z_k - z_{k-1}),   rho u_k in lam d||z_k||_1,
# so the optimality residual AT b_k is the dual residual (N = 1 block), and
# moving the gradient from b_k to ``coef_`` = z_k adds at most L x primal, L
# = lambda_max(X1^T X1 / n) / 4 = 0.26-0.29 here: (1 + L) tol + 1e-6 of
# float32 summation < 1.3 tol in the 2-norm. A run whose stop the DUAL
# residual decides sits near that ceiling (CPU, 8,192 x 32 and 4,096 x 256 on
# meshes of 1 and 4: 2.2e-5 - 8.9e-5; the tier-1 tests hold the estimator to
# 2 x tol there). The cell's runs do not: rho ends at 0.25, the PRIMAL
# residual decides the stop (7.2e-5 - 9.5e-5) with the dual one at 7e-6 -
# 1e-5, and the largest ENTRY of the residual at z is H (z - b) ~ 0.15 x one
# entry of the primal residual.
# The limit lies between the two readings:
#   chip, the program, the last fit of each of 23 runs: 1.3e-6 - 2.7e-6
#   chip, the control (bf16_design, seeds 2147537603 and 2147539301): 1.27e-4
#     and 1.28e-4 — the coefficient staircase: an active 0.33 rounds by up
#     to 1e-3, times the curvature 0.15; CPU at 16,384 rows 1.27e-4 -
#     1.39e-4, so it does not move with n
# KKT_BAND = 4e-5: 15 x the program's largest, a third of the control's. It
# is not lower because the CPU rehearsal (2,048 x 256, where lam is a
# quarter of a null entry's deviation, ~200 features are active and the DUAL
# residual decides the stop) reads 2.0e-5 - 2.6e-5 over three seeds and has
# to pass the same check.
#   chip, the reference's 4-block ADMM (admm_faults.py, its runs stopped at
#     a tenth of tol): run rightly 4.4e-6 (stopped at tol itself, where the
#     DUAL residual decides its stop: 4.7e-5); a penalised intercept 1.96e-3
#     and 2.00e-3 (= lam: its gradient entry rests at the threshold), the
#     threshold without the 1 / N 5.86e-3 and 5.89e-3 — fifty and a hundred
#     and fifty times the limit
# What no limit on the fit can tell: a design rounded to bfloat16 with the
# coefficients left in float32. Its roundings are independent and of zero
# mean and a gradient entry averages them over 4,194,304 rows: ~3e-7 (PERF.md
# section 6), under the program's own readings.
KKT_BAND = 4e-5

# --- the objective against the reference's OWN optimum ----------------------
# The reference finds its optimum over ALL the cell's rows by proximal
# gradient from coef_ (``references/logreg_l1.py::optimum``, to a KKT
# residual of 1e-6). Both objectives are float32 means over the same rows in
# the same blocks; their difference carries ~1e-7 of summation noise either
# way, so the excess may read slightly negative. Between the two readings:
#   chip, the program: -4.9e-8 - +6.5e-8 (23 runs)
#   chip, the control: 4.6e-7 and 5.8e-7 (CPU at 16,384 rows: 6.2e-7, 7.6e-7)
# EXCESS_BAND = 2.5e-7 either side of zero: four times the program's
# largest, a little over half the control's; the CPU rehearsal reads 1.3e-7 -
# 1.5e-7. (Theory agrees with the order: a point whose KKT residual is r lies
# within r^2 / (2 mu) of the optimum, mu >= 0.02 along the teacher.)
#   chip, the reference's ADMM run rightly 2.6e-8; a penalised intercept
#     1.28e-5 and 1.42e-5, no 1 / N 6.3e-3 (both fail)
EXCESS_BAND = 2.5e-7

# --- the support -------------------------------------------------------------
# ``coef_`` must hold exact zeros (the soft threshold's, not small numbers),
# and its support must equal the reference optimum's — except on entries the
# reference itself puts within the KKT limit of the threshold, where a fit
# inside that limit may fall either side: a ZERO of the reference whose
# gradient entry has |g_j| >= lam - KKT_BAND (nearly active), or a NON-ZERO
# with |coef_j| <= KKT_BAND / SUPPORT_MU (barely active; SUPPORT_MU = 0.1 is
# a floor of the per-coordinate curvature E[p (1 - p)] ~ 0.15-0.2). Such
# entries are counted (``support_near``) and excused; any other mismatch
# fails. On the cell's data none is expected: lam is ~9 deviations of a null
# feature's gradient entry, the 32 active coefficients are ~0.33.
#   chip, the program, 23 runs: nnz 32 = the reference optimum's = the
#     teacher's support, near 0, mismatches 0; |coef_ - optimum| <= 1.5e-5
SUPPORT_MU = 0.1

# --- predict_proba -------------------------------------------------------------
# ``tolerances.TOL_PROBA`` (1e-3, PR 22), the accepted GLM cells' limit on
# ``glm.decision``, the program they share with this cell: an f32 matvec on
# the VPU, 0.0 from the reference's on 65,536 rows in all 23 chip runs; ONE
# bf16 pass moves eta by ~2^-9 |eta| and a probability by 5.3e-3 (chip, the
# reference's proba from a bf16-rounded X and coef). It judges the decision
# program at the fit's own ``coef_``, not the fit.
