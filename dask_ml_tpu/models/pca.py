"""PCA / TruncatedSVD / IncrementalPCA via distributed SVD.

Reference: ``dask_ml/decomposition/{pca,truncated_svd,incremental_pca}.py``
(SURVEY.md §2a rows PCA/TruncatedSVD/IncrementalPCA, §3.3 call stack).
The reference lowers to ``da.linalg.svd`` (TSQR task graph) or
``svd_compressed`` (Halko); here those are the single-program TSQR /
randomized SVD kernels in ``ops/linalg.py`` — a per-shard local factor
(guarded CholeskyQR2, Householder where the guard fails;
``solver_info_["qr_fallbacks"]`` counts those) + ICI all-gather,
psum-reduced matmul passes, small replicated SVD.

Centering: padded rows must stay exactly zero after ``X - mean_``, so the
centered matrix is re-masked before the SVD (zero rows leave R/range
unchanged). The resident fit centres INSIDE its solver program
(``pca.rsvd`` / ``pca.svd_tall``): an eager ``(X - mean) * mask`` holds two
X-sized buffers beside X, which at one chip's share of the tall-skinny
deployment (2,097,152 x 512 float32, 4 GiB) is most of the chip.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..base import BaseEstimator, TransformerMixin, to_host
from ..observability import span, track_program
from ..ops import linalg
from ..ops.reductions import masked_mean_var
from ..parallel.sharded import ShardedArray
from ..utils.validation import check_array, check_is_fitted


def _resolve_n_components(n_components, n, d):
    if n_components is None:
        return min(n, d)
    if isinstance(n_components, float) and not n_components.is_integer():
        raise ValueError(
            "float n_components means a variance fraction and requires "
            "svd_solver='full'"
        )
    n_components = int(n_components)
    if not 0 < n_components <= min(n, d):
        raise ValueError(
            f"n_components={n_components} must be in (0, {min(n, d)}]"
        )
    return n_components


@partial(jax.jit, static_argnames=("mxu_dtype",))
def _block_pca_moments(X, mask, shift, mxu_dtype=None):
    """Per-block (Σ(x-shift), Σ(x-shift)(x-shift)T), padded rows masked.
    ``shift`` is a rough mean estimate: centering the accumulation keeps
    the f32 block sums ~O(n_b·std²) instead of O(n_b·mean²), avoiding
    catastrophic cancellation in cov = G - n·μμᵀ for data with
    mean ≫ std (the blocks are f64-accumulated on host afterwards).

    ``mxu_dtype=bfloat16`` (config.dtype): the Gram outer product — the
    pass's FLOPs — runs at bf16 with f32 accumulation on CENTERED data
    (small magnitudes, so bf16's ~3 significant digits bound the
    covariance's relative error at ~1e-2; component parity tolerances in
    the tests reflect that). Mean sums stay at input precision."""
    xc = X - shift
    xm = xc * mask[:, None]
    if mxu_dtype is not None and X.dtype != mxu_dtype:
        g = jnp.einsum("ni,nj->ij", xm.astype(mxu_dtype),
                       xc.astype(mxu_dtype),
                       preferred_element_type=jnp.float32)
    else:
        g = jnp.einsum("ni,nj->ij", xm, xc,
                       preferred_element_type=jnp.float32)
    return jnp.tensordot(mask, xc, axes=(0, 0)), g


@track_program("pca.center")
@jax.jit
def _mean_var(x, mask, n_rows):
    """(mean, unbiased variance) per feature in two fused passes over x:
    plain f32 sums on the vector units (a ``tensordot`` with the mask would
    round x to bf16 on a TPU's MXU), no X-sized temporary."""
    m = mask[:, None]
    mean = jnp.sum(x * m, axis=0) / n_rows
    xc = (x - mean) * m
    return mean, jnp.sum(xc * xc, axis=0) / jnp.maximum(n_rows - 1, 1)


_MEAN_VAR_SWEEPS = 2     # passes over x that _mean_var makes


def _centered(x, mask, mean):
    return (x - mean) * mask[:, None]


@track_program("pca.rsvd")
@partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _rsvd_fit(x, mask, mean, key, k, n_iter, mesh, want_u):
    """Centre, Halko randomized SVD, V-based signs: one program. ``u`` only
    where the caller wants scores (``fit`` alone never forms it). Last: how
    many of the tall QRs had a shard fall back to Householder."""
    u, s, vt, fallbacks = linalg.randomized_svd(
        _centered(x, mask, mean), k, key, mesh, n_iter=n_iter)
    u, vt = linalg.svd_flip(u, vt)
    return (u if want_u else None), s, vt, fallbacks


@track_program("pca.svd_tall")
@partial(jax.jit, static_argnums=(3, 4))
def _svd_tall_fit(x, mask, mean, mesh, want_u):
    """Centre, exact SVD through TSQR, V-based signs: one program; last,
    whether a shard of its tall QR fell back to Householder."""
    u, s, vt, fallbacks = linalg.svd_tall(_centered(x, mask, mean), mesh)
    u, vt = linalg.svd_flip(u, vt)
    return (u if want_u else None), s, vt, fallbacks


@track_program("pca.transform")
@jax.jit
def _project(x, mask, mean, comp, scale):
    """Scores ``((x - mean) * mask) @ comp.T / scale`` in one fused pass
    over x, multiplied in f32 (``HIGHEST``): a score is a 512-term sum per
    row, where a single bf16 pass would leave ~3e-3 relative error."""
    scores = jnp.matmul(_centered(x, mask, mean), comp.T,
                        precision=jax.lax.Precision.HIGHEST)
    return scores if scale is None else scores / scale


class PCA(TransformerMixin, BaseEstimator):
    """Ref: dask_ml/decomposition/pca.py::PCA."""

    def __init__(self, n_components=None, copy=True, whiten=False,
                 svd_solver="auto", tol=0.0, iterated_power=0,
                 random_state=None, fit_dtype=None):
        self.n_components = n_components
        self.copy = copy
        self.whiten = whiten
        self.svd_solver = svd_solver
        self.tol = tol
        self.iterated_power = iterated_power
        self.random_state = random_state
        # per-estimator precision override (None = config.dtype policy;
        # "float32" opts the streamed Gram out of the TPU bf16 default,
        # "bfloat16" forces it); resolved choice lands on fit_dtype_
        self.fit_dtype = fit_dtype

    def _solver(self, k, n, d):
        if self.svd_solver == "auto":
            # randomized when asking for a small fraction of a wide matrix
            # (sklearn-style heuristic); exact TSQR otherwise
            return "randomized" if k < 0.8 * min(n, d) and min(n, d) > 200 \
                else "full"
        if self.svd_solver in ("full", "tsqr"):
            return "full"
        if self.svd_solver == "randomized":
            return "randomized"
        raise ValueError(f"Unknown svd_solver {self.svd_solver!r}")

    def fit(self, X, y=None):
        from ..parallel.streaming import stream_plan

        block_rows = stream_plan(X)
        if block_rows is not None:
            return self._fit_streamed(X, block_rows)
        # the root span covers the whole resident call; its children
        # (fit.validate, fit.center, fit.solve, fit.finish) are the
        # phases, each ending where its host code ends
        with span("fit", component="PCA") as root:
            self._fit(X, root)
        return self

    def _fit_streamed(self, X, block_rows):
        """Out-of-core fit via one streamed moments pass: accumulate
        (Σx, ΣxxᵀX) per block, then eigendecompose the d×d covariance on
        host. For the tall-skinny shapes this estimator targets
        (d ≤ O(10³), BASELINE configs), the Gram route computes the FULL
        spectrum in a single pass — subsuming both the TSQR and
        randomized solvers of the resident path, with one pass where
        Halko needs two. Ref: the reference's ``da.linalg`` reductions
        over host-backed chunks (SURVEY.md §3.3)."""
        from ..parallel import distributed as dist
        from ..parallel.streaming import BlockStream, _slice_dense

        n, d = X.shape
        multi = dist.process_count() > 1
        if multi:
            # multi-host: X is the process-local shard; n/moments merge
            # globally so every process computes the identical global PCA
            n = int(dist.psum_host(np.asarray(float(n))))
        if n < d:
            raise ValueError(
                "PCA requires tall data (n_samples >= n_features); got "
                f"{n} x {d}"
            )
        frac = None
        if (isinstance(self.n_components, float)
                and 0.0 < self.n_components < 1.0):
            frac, k = self.n_components, min(n, d)
        else:
            k = _resolve_n_components(self.n_components, n, d)
        from .streamed_svd import STREAM_GRAM_MAX_D

        if frac is None and self._solver(k, n, d) == "randomized" and (
                self.svd_solver == "randomized"
                or d > STREAM_GRAM_MAX_D):
            # the O(d·k') randomized path (ISSUE 18 layer 3): explicit
            # solver choice, or auto once the d×d Gram stops being the
            # cheap one-pass answer (wide d — the feature-sharded
            # regime on a 2-D mesh)
            return self._fit_streamed_randomized(X, block_rows, k, n, d)
        stream = BlockStream((X,), block_rows=block_rows)
        # shift estimate from a small head slice (exactness not needed —
        # any shift near the mean kills the cancellation, but it must be
        # IDENTICAL on every process: block sums with different shifts
        # cannot merge); _slice_dense handles sparse sources
        head = _slice_dense(X, 0, min(4096, X.shape[0]), np.float64)
        if multi:
            hs, hn = dist.psum_host(head.sum(axis=0),
                                    np.asarray(float(len(head))))
            shift = hs / max(float(hn), 1.0)
        else:
            shift = head.mean(axis=0)
        shift_dev = jnp.asarray(shift, jnp.float32)
        from ..config import fit_dtype_info, mxu_dtype

        mxu = mxu_dtype(getattr(self, "fit_dtype", None))
        # resolved precision on record (auto falls back to f32 off-TPU)
        self.fit_dtype_ = fit_dtype_info(
            getattr(self, "fit_dtype", None)
        )["fit_dtype"]
        s = np.zeros(d, np.float64)
        g = np.zeros((d, d), np.float64)
        for blk in stream:
            bs, bg = _block_pca_moments(blk.arrays[0], blk.mask,
                                        shift_dev, mxu_dtype=mxu)
            s += np.asarray(bs, np.float64)
            g += np.asarray(bg, np.float64)
        if multi:
            s, g = dist.psum_host(s, g)
        mean_c = s / n  # mean of the SHIFTED data
        mean = shift + mean_c
        cov = (g - n * np.outer(mean_c, mean_c)) / (n - 1)
        evals, evecs = np.linalg.eigh(cov)
        order = np.argsort(evals)[::-1]
        ev = np.maximum(evals[order], 0.0)
        vt = evecs[:, order].T
        # deterministic signs, V-based (linalg.svd_flip convention)
        max_abs = np.argmax(np.abs(vt), axis=1)
        signs = np.sign(vt[np.arange(vt.shape[0]), max_abs])
        vt = vt * np.where(signs == 0, 1.0, signs)[:, None]

        total_var = float(ev.sum())
        if frac is not None:
            ratio = np.cumsum(ev / total_var)
            k = int(np.searchsorted(ratio, frac) + 1)
        self.n_components_ = k
        self.components_ = vt[:k]
        self.explained_variance_ = ev[:k]
        self.explained_variance_ratio_ = ev[:k] / total_var
        self.singular_values_ = np.sqrt(ev[:k] * (n - 1))
        self.mean_ = mean
        if k < min(n, d):
            self.noise_variance_ = max(
                (total_var - ev[:k].sum()) / (min(n, d) - k), 0.0
            )
        else:
            self.noise_variance_ = 0.0
        self.n_features_in_ = d
        self.n_samples_ = n
        # per-feature training profile for train-vs-serve drift scoring
        self.training_profile_ = stream.profile_snapshot()
        return self

    def _fit_streamed_randomized(self, X, block_rows, k, n, d):
        """Out-of-core randomized-SVD fit (ISSUE 18 layer 3): the
        range-finder passes stream through the super-block scan with a
        TSQR reduction over "data" (feature-sharded X tiles on a 2-D
        mesh), so device memory is O(d·k') where the Gram route holds
        a d×d covariance. See ``models/streamed_svd.py``."""
        from .streamed_svd import flip_signs_vt, streamed_randomized_svd

        # the streamed rSVD reducers accumulate f32 (the QR chain is
        # precision-bound — no bf16 flavor); on record for /status
        self.fit_dtype_ = "float32"
        key = jax.random.PRNGKey(
            0 if self.random_state is None else int(self.random_state)
        )
        size = min(k + 10, min(n, d))
        out = streamed_randomized_svd(
            X, block_rows, size, max(int(self.iterated_power), 2), key,
            center=True, n_rows_global=n,
        )
        vt = flip_signs_vt(out["vt"])
        s = out["s"]
        ev = s.astype(np.float64) ** 2 / (n - 1)
        total_var = float(out["var1"].sum())
        self.n_components_ = k
        self.components_ = vt[:k]
        self.explained_variance_ = ev[:k]
        self.explained_variance_ratio_ = ev[:k] / total_var
        self.singular_values_ = s[:k].astype(np.float64)
        self.mean_ = out["mean"]
        if k < min(n, d):
            self.noise_variance_ = max(
                (total_var - ev[:k].sum()) / (min(n, d) - k), 0.0
            )
        else:
            self.noise_variance_ = 0.0
        self.n_features_in_ = d
        self.n_samples_ = n
        self.training_profile_ = out["stream"].profile_snapshot()
        return self

    def _fit(self, X, root, want_u=False):
        """The resident fit. Returns (X, u, s, mask); ``u`` (flipped, on
        the device) is None unless ``want_u``."""
        with span("fit.validate"):
            X = check_array(X, dtype=np.float32)
            n, d = X.shape
            if n < d:
                raise ValueError(
                    "PCA requires tall data (n_samples >= n_features); got "
                    f"{n} x {d}"
                )
            frac = None
            if (isinstance(self.n_components, float)
                    and 0.0 < self.n_components < 1.0):
                # sklearn's variance-fraction API: needs the full spectrum
                if self._solver(min(n, d), n, d) != "full" and \
                        self.svd_solver not in ("auto", "full", "tsqr"):
                    raise ValueError(
                        "n_components as a variance fraction requires "
                        "svd_solver in ('auto', 'full', 'tsqr')"
                    )
                frac, k = self.n_components, min(n, d)
            else:
                k = _resolve_n_components(self.n_components, n, d)
            solver = "full" if frac is not None else self._solver(k, n, d)
            mask = X.row_mask(X.dtype)
            # the resident factorisation is float32 whatever the dtype
            # policy says (the QR chain is precision-bound); on record
            self.fit_dtype_ = "float32"
        root.add(n_rows=n)
        with span("fit.center", x_sweeps=_MEAN_VAR_SWEEPS):
            # dispatch only: the solver program takes the mean where it
            # lives and makes the centred copy itself
            mean, var = _mean_var(X.data, mask, jnp.asarray(n, X.dtype))
        with span("fit.solve", solver=solver) as sp:
            if solver == "full":
                size, n_iter, sweeps = min(n, d), 0, 1
                u, s, vt, fallbacks = _svd_tall_fit(X.data, mask, mean,
                                                    X.mesh, want_u)
            else:
                key = jax.random.PRNGKey(
                    0 if self.random_state is None else int(self.random_state)
                )
                # randomized_svd's own sketch width and sweep count
                size = min(k + 10, min(n, d))
                n_iter = max(int(self.iterated_power), 2)
                sweeps = linalg.randomized_svd_sweeps(n_iter)
                u, s, vt, fallbacks = _rsvd_fit(X.data, mask, mean, key, k,
                                                n_iter, X.mesh, want_u)
            sp.add(size=size, n_iter=n_iter, x_sweeps=sweeps)
            root.add(n_iter=n_iter)
            # the fetch of s is where the host waits for the program
            s_h = to_host(sp.sync(s)).astype(np.float64)
            vt_h = to_host(vt).astype(np.float64)
            mean_h = to_host(mean).astype(np.float64)
            total_var = float(np.sum(to_host(var), dtype=np.float64))
            qr_fallbacks = int(to_host(fallbacks))
            sp.add(qr_fallbacks=qr_fallbacks)
        with span("fit.finish"):
            if not np.isfinite(s_h).all():
                raise FloatingPointError(
                    "PCA produced non-finite singular values: the input "
                    "contains NaN/Inf"
                )
            ev = s_h ** 2 / (n - 1)
            if frac is not None:
                ratio = np.cumsum(ev / total_var)
                k = int(np.searchsorted(ratio, frac) + 1)
            self.n_components_ = k
            self.components_ = vt_h[:k]
            self.explained_variance_ = ev[:k]
            self.explained_variance_ratio_ = ev[:k] / total_var
            self.singular_values_ = s_h[:k]
            self.mean_ = mean_h
            if k < min(n, d):
                self.noise_variance_ = max(
                    (total_var - ev[:k].sum()) / (min(n, d) - k), 0.0
                )
            else:
                self.noise_variance_ = 0.0
            self.n_features_in_ = d
            self.n_samples_ = n
            # what carried the fit (the GLMs' and KMeans' solver_info_):
            # x_sweeps counts the solver program's products with X or X.T;
            # qr_fallbacks the tall QRs (of 1 + n_iter) in which a shard's
            # panel failed CholeskyQR2's guard and took Householder
            self.solver_info_ = {"solver": solver, "size": size,
                                 "n_iter": n_iter, "x_sweeps": sweeps,
                                 "qr_fallbacks": qr_fallbacks}
        return X, u, s, mask

    def fit_transform(self, X, y=None):
        from ..parallel.streaming import stream_plan

        block_rows = stream_plan(X)
        if block_rows is not None:
            # out-of-core: fit via the streamed moments pass, then the
            # streamed (block-wise) transform — X never materializes
            return self._fit_streamed(X, block_rows).transform(X)
        with span("fit", component="PCA") as root:
            X, u, s, mask = self._fit(X, root, want_u=True)
        k = self.n_components_
        scores = u[:, :k] * s[None, :k]
        if self.whiten:
            scores = scores * jnp.sqrt(jnp.asarray(self.n_samples_ - 1,
                                                   scores.dtype)) / s[None, :k]
        return ShardedArray(scores * mask[:, None], X.n_rows, X.mesh)

    def transform(self, X):
        check_is_fitted(self, "components_")
        from ..parallel.streaming import stream_plan, streamed_map

        block_rows = stream_plan(X)
        if block_rows is not None:
            # block-wise host→device→host scores; X never materializes
            comp = jnp.asarray(self.components_, jnp.float32)
            mean = jnp.asarray(self.mean_, jnp.float32)
            scale = (
                jnp.sqrt(jnp.asarray(self.explained_variance_, jnp.float32))
                if self.whiten else None
            )

            def block_scores(blk):
                sc = ((blk.arrays[0] - mean) * blk.mask[:, None]) @ comp.T
                return sc / scale if scale is not None else sc

            return streamed_map(X, block_rows, block_scores)
        # dispatch only: the scores stay on the device, nothing here waits
        with span("transform", component="PCA") as root:
            X = check_array(X, dtype=np.float32)
            root.add(n_rows=X.n_rows)
            scale = np.sqrt(np.asarray(self.explained_variance_, np.float32)) \
                if self.whiten else None
            scores = _project(
                X.data, X.row_mask(X.dtype),
                np.asarray(self.mean_, np.float32),
                np.asarray(self.components_, np.float32), scale,
            )
            return ShardedArray(scores, X.n_rows, X.mesh)

    def inverse_transform(self, X):
        check_is_fitted(self, "components_")
        X = check_array(X, dtype=np.float32)
        comp = jnp.asarray(self.components_, X.dtype)
        scores = X.data
        if self.whiten:
            scores = scores * jnp.sqrt(
                jnp.asarray(self.explained_variance_, X.dtype)
            )
        out = scores @ comp + jnp.asarray(self.mean_, X.dtype)
        out = out * X.row_mask(out.dtype)[:, None]
        return ShardedArray(out, X.n_rows, X.mesh)

    # -- probabilistic-PCA scoring (sklearn parity) -----------------------
    def _scoring_components(self):
        """(components, explained_variance) with sklearn's whiten
        adjustment: whitened components_ are unit-scaled, so the model
        covariance needs them rescaled by sqrt(ev)."""
        comp = np.asarray(self.components_, np.float64)
        ev = np.asarray(self.explained_variance_, np.float64)
        if getattr(self, "whiten", False):
            comp = comp * np.sqrt(ev)[:, None]
        return comp, ev

    def get_covariance(self):
        """cov = components_ᵀ diag(ev - σ²) components_ + σ² I (small,
        d×d, host — the data-sized work stays on device in score_samples)."""
        check_is_fitted(self, "components_")
        comp, ev = self._scoring_components()
        sigma2 = float(self.noise_variance_)
        cov = (comp.T * np.maximum(ev - sigma2, 0.0)) @ comp
        cov[np.diag_indices_from(cov)] += max(sigma2, 0.0)
        return cov

    def get_precision(self):
        check_is_fitted(self, "components_")
        d = self.components_.shape[1]
        sigma2 = float(self.noise_variance_)
        if sigma2 <= 0.0:  # incl. roundoff-negative: Woodbury would flip sign
            return np.linalg.pinv(self.get_covariance())
        # Woodbury (sklearn's formula): avoids inverting the full cov
        comp, ev = self._scoring_components()
        scaled = comp * np.sqrt(np.maximum(ev - sigma2, 0.0))[:, None]
        k = comp.shape[0]
        inner = scaled @ scaled.T / sigma2 + np.eye(k)
        precision = (np.eye(d) - scaled.T @ np.linalg.solve(inner, scaled)
                     / sigma2) / sigma2
        return precision

    def score_samples(self, X):
        """Per-sample log-likelihood under the probabilistic PCA model
        (ref: sklearn/dask-ml PCA.score_samples). The d×d precision is
        host math; the (n, d) quadratic form runs sharded on device."""
        check_is_fitted(self, "components_")
        precision = self.get_precision()
        d = np.shape(X)[1]
        sign, logdet = np.linalg.slogdet(precision)
        const = -0.5 * (d * np.log(2.0 * np.pi) - sign * logdet)
        from ..parallel.streaming import stream_plan, streamed_map

        block_rows = stream_plan(X)
        if block_rows is not None:  # out-of-core: block-wise quadratic form
            mean = jnp.asarray(self.mean_, jnp.float32)
            prec = jnp.asarray(precision, jnp.float32)

            def block_ll(blk):
                xc = (blk.arrays[0] - mean) * blk.mask[:, None]
                return -0.5 * jnp.sum((xc @ prec) * xc, axis=1) + const

            return streamed_map(X, block_rows, block_ll)
        X = check_array(X, dtype=np.float32)
        xc = (X.data - jnp.asarray(self.mean_, X.dtype)) \
            * X.row_mask(X.dtype)[:, None]
        quad = jnp.sum(
            (xc @ jnp.asarray(precision, X.dtype)) * xc, axis=1
        )
        return to_host(-0.5 * quad + const)[: X.n_rows]

    def score(self, X, y=None):
        """Mean per-sample log-likelihood (sklearn parity)."""
        return float(np.mean(self.score_samples(X)))


class TruncatedSVD(TransformerMixin, BaseEstimator):
    """Ref: dask_ml/decomposition/truncated_svd.py::TruncatedSVD — same SVD
    backends as PCA, no centering (sparse-friendly semantics)."""

    def __init__(self, n_components=2, algorithm="tsqr", n_iter=5,
                 random_state=None, tol=0.0, compute=True):
        self.n_components = n_components
        self.algorithm = algorithm
        self.n_iter = n_iter
        self.random_state = random_state
        self.tol = tol
        self.compute = compute

    def fit(self, X, y=None):
        from ..parallel.streaming import stream_plan

        block_rows = stream_plan(X)
        if block_rows is not None:
            return self._fit_streamed(X, block_rows)
        self.fit_transform(X)
        return self

    def _fit_streamed(self, X, block_rows):
        """Out-of-core fit via the streamed randomized SVD (ISSUE 18
        layer 3) — NO centering, preserving the estimator's
        sparse-friendly semantics (sparse sources stream densified
        blocks; X never materializes whole)."""
        n, d = int(X.shape[0]), int(X.shape[1])
        k = self.n_components
        if not 0 < k < d:
            raise ValueError(f"n_components={k} must be in (0, {d})")
        if self.algorithm != "randomized":
            raise ValueError(
                "streamed TruncatedSVD requires algorithm='randomized' "
                "(the exact TSQR factorization needs the resident "
                f"matrix); got algorithm={self.algorithm!r}"
            )
        from .streamed_svd import flip_signs_vt, streamed_randomized_svd

        key = jax.random.PRNGKey(
            0 if self.random_state is None else int(self.random_state)
        )
        size = min(k + 10, min(n, d))
        out = streamed_randomized_svd(
            X, block_rows, size, max(int(self.n_iter), 1), key,
            center=False,
        )
        n = out["n"]
        vt = flip_signs_vt(out["vt"])[:k]
        s = out["s"][:k].astype(np.float64)
        # score-column variance WITHOUT a scores pass: the scores are
        # XV, so E[(xv_j)²] = s_j²/n (VᵀXᵀXV = S²) and the score means
        # come from the moments pass's data mean
        sc_mean = out["mean"] @ vt.T
        ev = np.maximum(s ** 2 / n - sc_mean ** 2, 0.0)
        self.components_ = vt
        self.explained_variance_ = ev
        self.explained_variance_ratio_ = ev / float(out["var0"].sum())
        self.singular_values_ = s
        self.n_features_in_ = d
        return self

    def fit_transform(self, X, y=None):
        from ..parallel.streaming import stream_plan

        block_rows = stream_plan(X)
        if block_rows is not None:
            # out-of-core: streamed fit, then the block-wise transform
            # (X never materializes)
            return self._fit_streamed(X, block_rows).transform(X)
        X = check_array(X, dtype=np.float32)
        n, d = X.shape
        k = self.n_components
        if not 0 < k < d:
            raise ValueError(f"n_components={k} must be in (0, {d})")
        mask = X.row_mask(X.dtype)
        data = X.data * mask[:, None]
        if self.algorithm == "tsqr":
            if n < d:
                raise ValueError("tsqr algorithm requires n_samples >= n_features")
            u, s, vt = linalg.svd_tall_jit(data, X.mesh)
        elif self.algorithm == "randomized":
            key = jax.random.PRNGKey(
                0 if self.random_state is None else int(self.random_state)
            )
            u, s, vt = linalg.randomized_svd_jit(
                data, k, key, X.mesh, n_iter=self.n_iter
            )
        else:
            raise ValueError(f"Unknown algorithm {self.algorithm!r}")
        u, vt = linalg.svd_flip(u, vt)
        u, s, vt = u[:, :k], s[:k], vt[:k]
        scores = u * s[None, :]

        # explained variance of the scores (sklearn semantics)
        sc_mean = jnp.sum(scores * mask[:, None], axis=0) / n
        ev = jnp.sum(((scores - sc_mean) ** 2) * mask[:, None], axis=0) / n
        _, full_var = masked_mean_var(X.data, mask, n, ddof=0)
        self.components_ = to_host(vt).astype(np.float64)
        self.explained_variance_ = to_host(ev).astype(np.float64)
        self.explained_variance_ratio_ = self.explained_variance_ / float(
            jnp.sum(full_var)
        )
        self.singular_values_ = to_host(s).astype(np.float64)
        self.n_features_in_ = d
        return ShardedArray(scores, X.n_rows, X.mesh)

    def transform(self, X):
        check_is_fitted(self, "components_")
        from ..parallel.streaming import stream_plan, streamed_map

        block_rows = stream_plan(X)
        if block_rows is not None:  # block-wise scores; X stays host-side
            comp = jnp.asarray(self.components_, jnp.float32)

            def block_scores(blk):
                return (blk.arrays[0] * blk.mask[:, None]) @ comp.T

            return streamed_map(X, block_rows, block_scores)
        X = check_array(X, dtype=np.float32)
        comp = jnp.asarray(self.components_, X.dtype)
        return ShardedArray(X.data @ comp.T, X.n_rows, X.mesh)

    def inverse_transform(self, X):
        check_is_fitted(self, "components_")
        X = check_array(X, dtype=np.float32)
        comp = jnp.asarray(self.components_, X.dtype)
        return ShardedArray(X.data @ comp, X.n_rows, X.mesh)


@jax.jit
def _ipca_update(components, singular, mean, n_seen, xb):
    """One incremental-PCA block update (Ross et al. 2008, as used by
    sklearn's IncrementalPCA): SVD of [S·Vt ; Xb - mean_b ; mean-correction]."""
    m = xb.shape[0]
    col_mean = jnp.mean(xb, axis=0)
    n_total = n_seen + m
    new_mean = (n_seen * mean + m * col_mean) / n_total
    corr = jnp.sqrt(n_seen * m / n_total) * (mean - col_mean)
    stack = jnp.concatenate(
        [singular[:, None] * components, xb - col_mean, corr[None, :]], axis=0
    )
    u, s, vt = jnp.linalg.svd(stack, full_matrices=False)
    return vt, s, new_mean, n_total


@jax.jit
def _block_sums(xb, shift):
    """(Σ(x−s), Σ(x−s)²) of one device block. The shift (≈ the data
    mean, taken from the first block) keeps the f32 sum-of-squares away
    from the E[x²]−E[x]² cancellation that corrupts variance for
    uncentered data; variance is shift-invariant so any s near the mean
    suffices. Cross-block accumulation upcasts to f64 on host."""
    c = xb - shift
    return jnp.sum(c, axis=0), jnp.sum(c * c, axis=0)


class IncrementalPCA(PCA):
    """Ref: dask_ml/decomposition/incremental_pca.py::IncrementalPCA —
    sequential partial_fit over blocks. Here each block update is one jitted
    program; ``fit`` streams the shards of a ShardedArray in order."""

    def __init__(self, n_components=None, whiten=False, copy=True,
                 batch_size=None, svd_solver="auto", iterated_power=0,
                 random_state=None):
        self.n_components = n_components
        self.whiten = whiten
        self.copy = copy
        self.batch_size = batch_size
        self.svd_solver = svd_solver
        self.iterated_power = iterated_power
        self.random_state = random_state

    def _blocks(self, X):
        """Sequential blocks WITHOUT materializing X (VERDICT r4 weak
        #4 — this used to start with ``X.to_numpy()``, an O(n·d) host
        gather of exactly the data the class exists to stream): device
        inputs yield device row slices (no host round-trip at all);
        host inputs (ndarray / memmap / sparse CSR) yield densified
        O(block) slices through the streaming layer's slicer."""
        n, d = int(X.shape[0]), int(X.shape[1])
        bs = self.batch_size or max(n // 10, 5 * d)
        if isinstance(X, ShardedArray):
            n = X.n_rows
            for i in range(0, n, bs):
                yield X.data[i:min(i + bs, n)]
            return
        from ..parallel.streaming import _slice_dense, as_row_sliceable

        X = as_row_sliceable(X)  # once, not per block slice
        for i in range(0, n, bs):
            yield _slice_dense(X, i, min(i + bs, n), np.float32)

    def partial_fit(self, X, y=None, check_input=True):
        self._reject_multihost()
        import scipy.sparse as sp

        if isinstance(X, ShardedArray):
            xb = X.data[: X.n_rows].astype(jnp.float32)
        elif isinstance(X, jax.Array):
            xb = X.astype(jnp.float32)
        elif sp.issparse(X):
            # a CSR block from the Incremental wrapper's sparse loop:
            # densify THIS block only (cast-before-toarray)
            from ..parallel.streaming import _slice_dense

            xb = jnp.asarray(
                _slice_dense(X.tocsr(), 0, X.shape[0], np.float32)
            )
        else:
            xb = jnp.asarray(np.asarray(X, dtype=np.float32))
        d = int(xb.shape[1])
        k = self.n_components or d
        if not hasattr(self, "n_samples_seen_") or self.n_samples_seen_ == 0:
            self._components = jnp.zeros((k, d), jnp.float32)
            self._singular = jnp.zeros((k,), jnp.float32)
            self._mean = jnp.zeros((d,), jnp.float32)
            self.n_samples_seen_ = 0
        vt, s, mean, n_total = _ipca_update(
            self._components, self._singular, self._mean,
            jnp.asarray(self.n_samples_seen_, jnp.float32), jnp.asarray(xb),
        )
        self._components, self._singular, self._mean = vt[:k], s[:k], mean
        self.n_samples_seen_ = int(n_total)
        self._finalize(d, k)
        return self

    def _finalize(self, d, k):
        n = self.n_samples_seen_
        self.components_ = to_host(self._components).astype(np.float64)
        self.singular_values_ = to_host(self._singular).astype(np.float64)
        self.mean_ = to_host(self._mean).astype(np.float64)
        self.explained_variance_ = self.singular_values_ ** 2 / max(n - 1, 1)
        self.n_components_ = k
        self.n_features_in_ = d
        # partial_fit streams never see total variance; fit() refines
        # this from the full-pass variance
        if not hasattr(self, "noise_variance_"):
            self.noise_variance_ = 0.0

    def fit_transform(self, X, y=None):
        # PCA.fit_transform would run the batch SVD path; the incremental
        # algorithm must fit block-wise then transform
        return self.fit(X, y).transform(X)

    @staticmethod
    def _reject_multihost():
        from ..parallel import distributed as dist

        if dist.process_count() > 1:
            # the incremental SVD update is SEQUENTIAL and
            # order-dependent — it cannot psum across shards; PCA's
            # streamed moments fit is the multi-host path
            raise NotImplementedError(
                "IncrementalPCA is single-process; use PCA (streamed "
                "moments psum globally) under a multi-host runtime"
            )

    def fit(self, X, y=None):
        self._reject_multihost()
        if hasattr(self, "n_samples_seen_"):
            del self.n_samples_seen_
        if not hasattr(X, "shape"):  # sklearn-style array-likes (lists)
            X = np.asarray(X, dtype=np.float32)
        if int(X.shape[0]) == 0:
            raise ValueError(
                "Found array with 0 sample(s) while a minimum of 1 is "
                "required by IncrementalPCA"
            )
        # the ratio needs the global per-feature variance; accumulate
        # (n, Σ(x−s), Σ(x−s)²) from the SAME blocks the incremental
        # updates consume — no second full-X placement (the old path ran
        # check_array over all of X, defeating out-of-core fits). The
        # shift (first block's mean) guards the f32 device sums against
        # catastrophic cancellation on uncentered data.
        s1 = s2 = shift = None
        n = 0
        for block in self._blocks(X):
            self.partial_fit(block)
            if isinstance(block, jax.Array):
                if shift is None:
                    shift = jnp.mean(block, axis=0)
                b1, b2 = _block_sums(block, shift)
            else:
                if shift is None:
                    shift = block.mean(axis=0, dtype=np.float64)
                c = block.astype(np.float64) - shift
                b1, b2 = c.sum(axis=0), np.square(c).sum(axis=0)
            b1 = np.asarray(b1, np.float64)
            b2 = np.asarray(b2, np.float64)
            s1 = b1 if s1 is None else s1 + b1
            s2 = b2 if s2 is None else s2 + b2
            n += int(block.shape[0])
        var = (s2 - s1 * s1 / n) / max(n - 1, 1)
        if not np.all(np.isfinite(var)):
            # the variance accumulators see every value, so this is the
            # streamed equivalent of check_array's finiteness gate
            raise ValueError("X contains NaN or infinity")
        total_var = float(np.sum(np.maximum(var, 0.0)))
        self.explained_variance_ratio_ = self.explained_variance_ / total_var
        k, d = self.n_components_, self.n_features_in_
        denom = min(n, d) - k
        self.noise_variance_ = (
            max(total_var - self.explained_variance_.sum(), 0.0) / denom
            if denom > 0 else 0.0
        )
        self.n_samples_ = n
        return self
