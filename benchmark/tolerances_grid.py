"""The bands of the ``gridsearch`` cell that ``tolerances.py`` does not
already give, each beside its reason. Every (fold, C) model is held to
``tolerances.py``'s logreg bands at its own fold's rows, the refit and
``predict_proba`` to the ``glm`` family's (``TOL_PROBA``)."""

# A test row's answer may differ from the reference's only where the
# reference's own decision value lies within this of the boundary, and a
# model's right answers on its test fold may differ by at most the count of
# such rows. Set between two readings at the cell's 4,194,304 rows on one
# v5e (the check's ``near_band_needed_max``: the least band that lets every
# one of the 50 scores through; PERF.md section 6): the search as it
# runs — both sides compute eta at float32 precision (the program's scoring
# product at ``HIGHEST``, the reference at ``highest``), so at most a row a
# model differs, one with |eta| under 1e-7 — and the same models scored at
# the nearest precision below, one bfloat16 pass of the product
# (``tools/grid_faults.py``'s ``bf16_scores``), which moves eta by ~2^-9
# sum_j |x_j coef_j| and nets ~50-60 rows a model, needing 1.5e-4 and more.
# 2e-5 sits ~8 times under that and far over the first; it holds ~11-16
# rows of a fold's 838,861.
NEAR_TIE = 2e-5

# The winner's rule the program states (``_BaseSearchCV.tie_tol``): the
# earliest candidate whose mean test score is within this of the best. The
# reference applies it to its own scores; where a candidate's mean lies
# within the near-tie slack of the rule's threshold, either choice is
# right.
TIE_TOL = 1e-3
