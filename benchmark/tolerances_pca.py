"""The bands that decide ``correct`` for the ``pca`` family, each beside its
reason. Functions of the shape (n, d, k, sketch width, power iterations) and
of the REFERENCE's own spectrum, so the same bands serve the cell at
2,097,152 x 512 and the CPU tests at a few thousand rows.

Two kinds of error meet here. The randomized solver's is ALGORITHMIC and
set by the spectrum: with sketch width ``l = k + p`` and ``q`` power
iterations, component ``i`` is found up to a tangent of about
``sqrt(A) * rho_i^(q + 1/2)``, ``rho_i = lambda_(k+1) / lambda_i`` the
eigenvalue ratio to the first one left out and ``A = (d - k) / (p - 1)``
the expected squared norm of Halko's ``Omega_2 pinv(Omega_1)`` per component
(Halko, Martinsson, Tropp 2011, Theorem 10.6: spectral error at most
``[1 + sqrt(k / (p - 1)) + e sqrt(k + p) / p * sqrt(min(m, n) - k)]^(1 /
(2q + 1)) * sigma_(k+1)``). Ritz values are second order in the tangent:
``A * rho_i^(2q + 1)``. A float64 simulation of the very iteration on the
configuration's covariance (d = 512, k = 64, l = 74, q = 2, planted
eigenvalues 64 .. 16 over unit noise, so rho_64 = 1.03 / 16) gives, worst of
six sketches: eigenvalue 64 off by 1.13e-4 relative (2.0 x A rho^5), the
top eight by 1.4e-7, the largest principal angle 3.9e-3 rad (0.52 x sqrt(A)
rho^2.5), captured variance short by 2.4e-7. On the chip (eight runs of
the cell, my chip runs, PR 25) the weakest eigenvalues read up to 2.0e-4
(5.0 x A rho^5) and the angle up to 4.4e-3 (0.6 x sqrt(A) rho^2.5): the
constants below are three times those readings — one failed check refuses
a PR, and the teeth stay: a solver that dropped a power iteration (rho^3
for rho^5: 240 times the error at rho = 0.064) lands far outside.

The other kind is ROUNDING, and it is what the stated precision (float32)
decides. Sums over the n rows average independent roundings away, so at
n = 2,097,152 a bf16 multiply moves an eigenvalue by ~1e-5 only; a score is
a sum over the d = 512 features of ONE row and keeps its rounding whole:
~2^-9 * sqrt(2) relative to the score scale in bf16, ~1e-6 in f32. The
``transform`` band is therefore the limit that a lower precision fails.
"""

import numpy as np

# constants over the worst case simulated (2.0, 0.52, 0.2) and read on the
# chip (5.0, 0.6, under its floor)
C_EIGENVALUE = 16.0
C_ANGLE = 2.0
C_CAPTURED = 1.0


def sketch_factor(d, k, size):
    return (d - k) / max(size - k - 1, 1)


def _rho(eigenvalues, k):
    lam = np.asarray(eigenvalues, np.float64)
    return np.minimum(lam[k] / lam[:k], 1.0) if k < len(lam) \
        else np.zeros(k)


# explained_variance_[i] against lambda_i, relative: the Ritz value's
# algorithmic error plus a float32 floor. The floor is the QR's: XLA's f32
# Householder QR of a 2,097,152-row panel leaves Q orthonormal to 2.5e-5
# (largest entry of Q^T Q - I, my chip run, PR 25; column norms are f32 sums
# over all the rows), s_i^2 inherits twice that, and the top eigenvalues
# (rho^5 ~ 1e-9) read 3.8e-5 - 3.9e-5 on the chip in every run, 1e-6 at a
# few thousand rows on the CPU. Five times the chip's reading. Precision
# does not move it at this row count: with every contraction at the TPU's
# default (one bf16 pass) the same fit read 9.7e-6, at HIGH 1.07e-5.
FLOOR_EIGENVALUE = 2e-4


def eigenvalue_band(eigenvalues, d, k, size, n_iter):
    return C_EIGENVALUE * sketch_factor(d, k, size) \
        * _rho(eigenvalues, k) ** (2 * n_iter + 1) + FLOOR_EIGENVALUE


# sine of the largest principal angle between span(components_) and the
# reference's span(V_k): first order in the tangent of the WORST component,
# the k-th. Floor: two f32 orthonormal bases of one subspace differ by
# ~sqrt(k) * 1e-6.
FLOOR_ANGLE = 1e-4


def angle_band(eigenvalues, d, k, size, n_iter):
    return C_ANGLE * np.sqrt(sketch_factor(d, k, size)) \
        * float(_rho(eigenvalues, k)[-1]) ** (n_iter + 0.5) + FLOOR_ANGLE


# trace(W C W^T) against sum(lambda[:k]), relative shortfall, judged by its
# size. By Ky Fan's inequality no orthonormal W captures MORE than
# sum(lambda[:k]), so it is >= 0 up to rounding (components_ orthonormal to
# ~1e-6; -7e-7 .. 1e-7 on the chip); what is missing is
# sum_i sin^2(theta_i) (lambda_i - noise), second order.
FLOOR_CAPTURED = 1e-5


def captured_band(eigenvalues, d, k, size, n_iter):
    lam = np.asarray(eigenvalues, np.float64)[:k]
    return C_CAPTURED * sketch_factor(d, k, size) * float(np.sum(
        _rho(eigenvalues, k) ** (2 * n_iter + 1) * lam) / np.sum(lam)) \
        + FLOOR_CAPTURED


# mean_ against the reference's, largest entry over the data's root mean
# square: two f32 sums over the same n rows. 0.9e-6 - 1.9e-6 at 2,097,152
# rows on the chip (eight runs, my chip runs, PR 25), 2e-7 on the CPU at a
# few thousand; ten times the chip's. (A bf16-rounded X averages out over
# the rows, 2^-9 / sqrt(n): the bf16 reference reads 1.7e-8 here at the
# cell's size — the mean is not the band that catches precision.)
TOL_MEAN = 2e-5

# ||components_ components_^T - I||, largest entry: rows of the Vt of an f32
# SVD of the (l x d) projection, stored as float64. 1.2e-6 on the CPU,
# 2.1e-6 - 3.2e-6 on the chip (my chip runs, PR 25); six times the latter.
TOL_ORTHONORMAL = 2e-5

# transform on the sample rows against (x - mean) @ components^T of the
# reference AT THE SYSTEM'S OWN components_ and mean_ (so the solver's
# algorithmic error, already judged above, does not enter), largest entry
# over the root mean square of the scores. f32 multiplies: ~sqrt(d) * 6e-8
# * |x||w| / score scale ~ 1e-6 at most; the program's fused pass at
# ``HIGHEST`` and the reference's read 0.0 apart on the chip and on the CPU
# (the same f32 arithmetic). The reference computed in bfloat16 — the
# nearest precision below the stated float32: rows and components rounded,
# one bf16 pass, f32 sums — reads 5.44e-3 at the cell's size on the chip, and
# so does an "f32" matmul at the TPU's default precision (my chip runs, PR
# 25, two seeds; every OTHER band passes that reference: its roundings
# average out over 2,097,152 rows). Three-pass bf16 (``HIGH``) reads 6.0e-5.
# The band sits between the two readings, 54 times under the bf16 one: a
# single bf16 multiply in ``transform`` fails, and only this limit fails it.
TOL_TRANSFORM = 1e-4

# explained_variance_ratio_: its denominator, the summed per-feature
# variance, against trace(C) — f32 sums of squares over n rows in one fused
# reduction: 1.14e-5 - 1.15e-5 at 2,097,152 rows on the chip in every run (my
# chip runs, PR 25; the reference's block sums, combined in float64, do not
# share it), 2e-8 on the CPU at a few thousand. Four times the chip's.
TOL_TOTAL_VARIANCE = 5e-5


def readings(ref, mean_, components_, explained_variance_,
             explained_variance_ratio_, size, n_iter):
    """{name: (reading, band)} of a fitted PCA's attributes against the
    exact reference ``ref`` (``references/pca.py::pca_exact``'s dict): every
    comparison above but ``transform``'s, which needs the rows. Every
    reading is a size (>= 0): it holds when it is at most its band."""
    lam, cov = ref["eigenvalues"], ref["cov"]
    W = np.asarray(components_, np.float64)
    k, d = W.shape
    ev = np.asarray(explained_variance_, np.float64)
    shape = (d, k, size, n_iter)
    rms = float(np.sqrt(np.trace(cov) / d + np.mean(ref["mean"] ** 2)))
    ev_band = eigenvalue_band(lam, *shape)
    worst = int(np.argmax(np.abs(ev - lam[:k]) / lam[:k] / ev_band))
    cosines = np.linalg.svd(W @ ref["components"].T, compute_uv=False)
    total = ev / np.asarray(explained_variance_ratio_, np.float64)
    return {
        "mean": (float(np.max(np.abs(mean_ - ref["mean"]))) / rms, TOL_MEAN),
        "orthonormal": (float(np.max(np.abs(W @ W.T - np.eye(k)))),
                        TOL_ORTHONORMAL),
        # the eigenvalue furthest out RELATIVE TO ITS OWN band
        "eigenvalue": (float(abs(ev[worst] - lam[worst]) / lam[worst]),
                       float(ev_band[worst])),
        "angle": (float(np.sqrt(max(0.0, 1.0 - float(cosines.min()) ** 2))),
                  angle_band(lam, *shape)),
        "captured": (float(abs(1.0 - np.trace(W @ cov @ W.T)
                               / lam[:k].sum())),
                     captured_band(lam, *shape)),
        "total_variance": (float(np.max(np.abs(total / np.trace(cov) - 1.0))),
                           TOL_TOTAL_VARIANCE),
    }


def transform_reading(scores, ref_scores):
    """Largest entry of ``scores - ref_scores`` over the root mean square of
    the reference's scores."""
    ref_scores = np.asarray(ref_scores, np.float64)
    return float(np.max(np.abs(np.asarray(scores, np.float64) - ref_scores))
                 / np.sqrt(np.mean(ref_scores ** 2)))
