"""The bands that decide ``correct`` in the ``hyperband`` family's cell, each
beside its reason and the readings it was set from. ``w`` is a model's
``(coef_, intercept_)``; a distance is ``||w - w_ref|| / ||w_ref||``
(``tolerances_sgd.distance``); a score is an accuracy on the ``n_test``
held-out rows, so one row is ``1 / n_test`` of it.

Chip readings: one v5e, 4,194,304 x 256, the whole ``max_iter=81`` search
(my chip runs, PR 32). The system's: every run of the cell (seeds in PERF.md
section 6). The faults': ``benchmark/tools/search_faults.py`` on seeds
2147490001 / 3200000011 (``chiprun_out/pr32/faults_*.jsonl``) — the
reference runs the whole search wrongly in ONE named way and its results go
through the cell's own check in place of the program's; every one of them
fails ``correct``, the reference run rightly passes:

  fault                          readings (two seeds): rows past a model's score band; the winner's ``stated``
  the BOTTOM third promoted      fails (B) at four cuts of four, exactly
  scored on training rows        454 / 158 rows (the models' median 89 rows off)
  every rung one call short      63 / 26 rows; 5.0e-3 / 8.6e-3
  the one-block partition        150 / 172 rows (median 138); 2.8e-2 / 2.9e-2
  blocks in reversed order       59 / 283 rows (median 66); 7.1e-4 / 5.8e-2
  weights rounded to bfloat16    127 / 40 rows (median 5); 3.9e-3 / 2.4e-2
  products rounded to bfloat16   41 / 16 rows (median 15 / 12); 1.12e-4 / 1.04e-4

CPU readings, in brackets: the rehearsal's 2,048 x 256 (8 blocks of 224 rows,
256 held-out rows), bfloat16 requested.
"""

from benchmark.tolerances_sgd import (TOL_MISMATCH_SHARE, TOL_TIE,  # noqa: F401
                                      distance)

# (a) the winner's weights against the reference's replay of that model AT
# THE STATED PRECISION (bfloat16 design and a bfloat16-rounded w in the eta
# product, everything else float32). System and reference multiply the same
# numbers; what is left is the system's float32 arithmetic — a cohort's
# gradient product is an (N, S) x (S, 256) matmul on the MXU at ``highest``
# (six bfloat16 passes), its sums in the MXU's order; a lone survivor's are
# the VPU fusions ``sgd.fused_epoch`` has. How far that carries depends on
# WHO WON. Nineteen runs of the cell: thirteen winners read 3.7e-6-8.7e-6
# (after 3, 9, 27 or 81 calls; ``sgd_incremental`` reads 6.8e-6-8.4e-6);
# five 81-call winners with a large step read 2.1e-5, 2.2e-5, 4.1e-5,
# 4.2e-5, 5.8e-5; one (eta0 0.42, alpha 7.7e-4: hardly damped, 81 steps)
# reads 1.52e-4 — MORE than the same model moves when the reference rounds
# its two products to bfloat16 (``lower`` 8.6e-5 there; 1.5e-4-9.2e-4 for
# the other seven winners it was read for, a hundred times their
# ``stated``). So neither a fixed band near the readings (5e-5 would have
# failed two runs of nineteen, 2e-5 — set from the first three — failed
# three of eight) nor a share of ``lower`` (it would have failed that one
# run) holds every winner, and this band is a coarse one: thirteen times
# over the worst reading, half the nearest fault it must fail (weights in
# bfloat16 3.9e-3 / 2.4e-2, a call short 5.0e-3 / 8.6e-3, the one-block
# partition 2.8e-2). The faults under it — reversed blocks on one seed of
# two (7.1e-4), the rounded products (1.04e-4-1.12e-4) — are (c)'s.
# ``lower`` stays a fact of every run.
TOL_STATED = 2e-3


# (b) against the float32 replay: what a bfloat16 design costs, as
# ``tolerances_sgd.f32_band`` has it — the x roundings average out over a
# block's S rows, the w rounding does not. Here the winner is whoever won:
# the system reads 2.3e-5-3.8e-5, but the reference's own two precisions lie
# 4.6e-5 and 2.4e-4 apart on the faults' two seeds (a winner with a large
# step size carries its roundings further), so the band has room for that:
# 6.0e-4 at the cell's S = 458,752 [9e-4 read at S = 224, band 2.3e-2]. It
# fails every fault but the rounded products (1.5e-4) and, on one seed of
# two, the reversed blocks (7.0e-4): loose on purpose, (a) guards the
# arithmetic, this one says the stated precision sits beside the exact one.
def f32_band(block_rows):
    return 1e-4 + 0.34 / float(block_rows) ** 0.5


# (c) every recorded final score against the accuracy of the reference's
# replay of that model on the reference's own held-out rows. The two differ
# only on rows whose decision lies within the arithmetic's reach of the
# boundary: a score may differ by the count of held-out rows the REPLAY
# puts within TIE x (the decision's rms) of zero, plus SLACK rows.
# TIE_STATED is far above what float32 arithmetic moves a decision by
# (1e-5 of the weights) and far below what a wrong model does; TIE_F32
# covers the bfloat16 design's own reach (a bfloat16 pass over the product
# moves a decision by 1.0e-2-1.3e-2 of the rms, tolerances_sgd.py).
# Readings: the recorded scores lie 9-32 rows from the stated replay's at
# the worst model (of 524,288) where 29-48 rows lie within TIE_STATED of the
# boundary — the worst model 10-26 rows INSIDE its band in all nineteen
# runs (mean 20, standard deviation 4.6) — and 40-60 rows from
# the float32 replay's against ~12,400 within TIE_F32. A fault's nearest
# miss is 16 rows PAST the band (the rounded products).
TIE_STATED = 1e-4
TIE_F32 = 3e-2
SLACK_ROWS = 2
# ... and over ALL the models the MEDIAN distance from the stated replay's
# score is 0 rows in every run (most candidates are insensitive: a small
# step, a strong penalty, few calls), where a precision below the stated
# one moves every model: the rounded products 15 rows, bfloat16 weights 5
# (seed 2147490001). At most MEDIAN_ROWS.
MEDIAN_ROWS = 3


def score_band(near_rows, n_test):
    return (float(near_rows) + SLACK_ROWS) / float(n_test)


# (d) the reference's OWN whole search from the same draw. A cut whose
# margin (the last kept score minus the first dropped one) is wider than
# ``cut_band`` must keep the same models; a narrower one may fall either
# way, and everything downstream of a cut that fell the other way is not
# compared. The band is twice (c)'s for a typical model: the rows a normal
# decision puts within TIE_STATED of zero (density 0.8 / rms) plus the
# slack, for each of the two models at the cut. At the cell's size it is
# 1.7e-4 (88 rows), and NO cut of any run so far is wider: 81 candidates'
# scores lie within a few 1e-3 of each other and neighbours in rank a few
# rows apart (``cuts_compared`` 0 of 10) — there the comparison with the
# reference's own search rests on ``best_score_``, and (B) and (C) hold
# every decision to the recorded scores and every score to the replay.
def cut_band(n_test):
    return 2.0 * (0.8 * TIE_STATED * n_test + SLACK_ROWS) / float(n_test)


# ... and ``best_score_`` against the reference's best: equal up to (c)
# where every cut fell alike; where a narrow cut fell the other way another
# candidate of nearly the same score wins. Three standard errors of an
# accuracy near 0.85 on n_test rows: 1.5e-3 at the cell's size, where the
# system's best and the reference's differ by 0-1.9e-6 (0 or 1 row).
def best_band(n_test):
    return 1.1 / float(n_test) ** 0.5
