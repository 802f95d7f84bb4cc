"""SpectralClustering via Nyström approximation.

Reference: ``dask_ml/cluster/spectral.py`` (SURVEY.md §2a
SpectralClustering row): exact affinity on an ``n_components``-row sample,
cross-affinity to the rest, orthogonalize, embed, then KMeans on the
embedding.

The equations. X is (n, d); Z = X[idx] are c = ``n_components`` landmark
rows drawn uniformly without replacement; k(x, z) the affinity (rbf:
``exp(-gamma ||x - z||^2)``).

- B = k(X, Z) (n, c, row-sharded); A = k(Z, Z) + ``NYSTROM_JITTER`` I;
- deg = B A^+ (B^T 1): the row sums of W~ = B A^+ B^T, which is never
  formed (rows whose degree is under ``TINY`` keep 1);
- G = diag(deg)^(-1/2) B A^(-1/2), so that the Nyström normalised affinity
  D^(-1/2) W~ D^(-1/2) is G G^T; thin SVD G = U S W^T, exact, no n x n
  matrix anywhere: W from the small factor of the distributed TSQR
  (``ops/linalg.py``), U's first k columns as G W_k S_k^(-1), S[:k] by a
  Rayleigh-Ritz step on those k directions;
- the embedding E is the first k = ``n_clusters`` columns of U, each ROW
  scaled to unit length (rows shorter than ``TINY`` stay);
- ``labels_`` is the best of ``n_init`` KMeans(k) fits on E by inertia;
  ``eigenvalues_`` is S[:k].

Departures from upstream: it takes the SVD of the normalised c x c block
and extends it (Fowlkes et al.'s one-shot form, no orthogonalisation over
the rows) and ignores ``n_init``; here the top singular vectors of G are
exact and ``n_init`` is honoured.

One fit is ONE embedding program (``spectral.embed``: the landmark draw,
both affinities, the degrees, G, the TSQR, the small SVD, the row scaling)
and ``n_init`` KMeans fits on its (n, k) output. Float32 throughout; the
rbf distances are taken after an exact re-centring on the landmarks' mean
(``ops/pairwise.py::recentred_distances_sq``) and every contraction asks
for ``Precision.HIGHEST``, as ``ops/linalg.py`` does. No X-sized and no
eager (n, c)-sized value exists. The memory contract, by XLA's own account
for a described v5e at 4,194,304 x 256, c = 100 (``benchmark/tools/
spectral_memory.py``): beside X the program's temporaries are 3.28 GiB,
2.1 dense (n, c) float32 panels of 1.56 GiB (B or the scaled B, and G; the
TSQR's Q is never formed: only its small factor is read), and its (n, 8)
output is dense (0.13 GiB).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..base import BaseEstimator, ClusterMixin
from ..observability import span, track_program
from ..ops import linalg, pairwise
from ..ops.reductions import top_l_path
from ..parallel.sharded import ShardedArray
from ..utils.validation import check_array
from .kmeans import KMeans, _gumbel_top_l
from .solvers.solvers import _fetch

# A = k(Z, Z) + NYSTROM_JITTER * I, and eigenvalues of A below it count as
# it: the Gram matrix of c points is positive semi-definite up to rounding
NYSTROM_JITTER = 1e-6
# a degree, or an embedding row's length, at or under TINY divides by 1
TINY = 1e-12
_PRECISION = linalg._PRECISION       # HIGHEST
_mm = linalg._mm            # a product at HIGHEST, as the factorisation's


def _affinity(name, x, z, gamma, degree, coef0, kernel_params=None):
    if callable(name):  # user kernel(X, Z, **kernel_params), ref contract
        return name(x, z, **(kernel_params or {}))
    if name == "rbf":
        return pairwise.rbf_kernel(x, z, gamma=gamma, precision=_PRECISION)
    if name == "polynomial":
        return pairwise.polynomial_kernel(x, z, degree=degree, gamma=gamma,
                                          coef0=coef0)
    if name == "sigmoid":
        return pairwise.sigmoid_kernel(x, z, gamma=gamma, coef0=coef0)
    if name == "linear":
        return pairwise.linear_kernel(x, z)
    raise ValueError(f"Unknown affinity {name!r}")


@track_program("spectral.embed")
@partial(jax.jit, static_argnames=("c", "k", "mesh", "affinity", "gamma",
                                   "degree", "coef0", "kernel_params"))
def _embed(X, mask, seed, *, c, k, mesh, affinity, gamma, degree, coef0,
           kernel_params):
    """(E (n, k) row-sharded with zero padding rows, S[:k], the c landmark
    rows' indices, whether the tall QR fell back to Householder). ``seed``
    is a uint32 scalar: the key is made here, so a new ``random_state`` is a
    new operand of the same program."""
    kern = partial(_affinity, affinity, gamma=gamma, degree=degree,
                   coef0=coef0,
                   kernel_params=dict(kernel_params) if kernel_params
                   else None)
    idx = _gumbel_top_l(mask, jax.random.PRNGKey(seed), c)
    Z = jnp.take(X, idx, axis=0)                       # (c, d) replicated
    B = kern(X, Z) * mask[:, None]                     # (n, c) row-sharded
    A = kern(Z, Z) + NYSTROM_JITTER * jnp.eye(c, dtype=X.dtype)
    w, V = jnp.linalg.eigh(A)
    w = jnp.maximum(w, NYSTROM_JITTER)
    inv_sqrt = _mm(V / jnp.sqrt(w), V.T)               # A^(-1/2)
    a_pinv = _mm(V / w, V.T)                           # A^+
    # the degrees: B A^+ (B^T 1), two matvecs (the padding rows of B are
    # zero, so its column sums are B^T 1 over the real rows)
    deg = _mm(B, _mm(a_pinv, jnp.sum(B, axis=0)))
    deg = jnp.where(deg > TINY, deg, 1.0)
    G = _mm(B * jax.lax.rsqrt(deg)[:, None], inv_sqrt)
    # the TSQR's small factor gives the right singular basis; the left
    # vectors come back as G V S^-1 from G itself, not as Q u_r: a landmark's
    # row of G is several times longer than its top-k part (its own
    # affinity is 1), and Q = G R^-1 carries that row's rounding through
    # R^-1 (condition ~14 here) before the projection drops it again
    _, r, fell_back = linalg.tsqr_counted(G, mesh)
    _, _, vt = jnp.linalg.svd(r, full_matrices=False)
    T = _mm(G, vt[:k].T)                               # (n, k) = U_k S_k
    # Rayleigh-Ritz on those k directions: R's Grams are MXU sums over all n
    # rows in float32 (3e-5 off at 4,194,304 rows, and the singular values
    # with them); this (k, k) Gram is a vector-unit tree sum, and its
    # eigenvalues are S[:k]^2 to second order in the basis's own error
    lam, W = jnp.linalg.eigh(jnp.sum(T[:, :, None] * T[:, None, :], axis=0))
    s = jnp.sqrt(jnp.maximum(lam[::-1], 0.0))
    E = _mm(T, W[:, ::-1]) / jnp.where(s > TINY, s, 1.0)
    norms = jnp.linalg.norm(E, axis=1, keepdims=True)
    E = E / jnp.where(norms > TINY, norms, 1.0) * mask[:, None]
    return E, s, idx, fell_back


class SpectralClustering(ClusterMixin, BaseEstimator):
    """Ref: dask_ml/cluster/spectral.py::SpectralClustering (the equations:
    this module's docstring).

    Fitted attributes: ``labels_`` (a row-sharded device ``ShardedArray`` of
    int32, which ``fit_predict`` returns as it is: ``.to_numpy()`` brings it
    to the host), ``eigenvalues_`` (the ``n_clusters`` largest SINGULAR
    VALUES of G, float64 on the host: the square roots of the Nyström
    normalised affinity's leading eigenvalues, all at most 1),
    ``landmarks_`` (the ``n_components`` row indices the affinity was taken
    to, a host int array), ``assign_labels_`` (the winning ``KMeans``),
    ``embedding_`` (``persist_embedding=True``: the (n, n_clusters) device
    ``ShardedArray`` the restarts clustered) and ``solver_info_``:
    ``embed`` (``"tsqr"``), ``precision``, ``qr_fallbacks`` (1 where a
    shard's CholeskyQR2 failed its guard and took Householder),
    ``restarts``, ``lloyd_iters`` (summed over the restarts; ``n_iters`` and
    ``inertias`` give each restart's, ``winner`` the index of the least
    inertia), ``assign_fused`` (the fused Lloyd kernel, not XLA's loop,
    carried the ``n_clusters``-wide table) and ``assign_layout`` (the
    winner's ``KMeans.solver_info_["lloyd_layout"]``: the table form that
    kernel read, ``None`` on XLA's loop)."""

    def __init__(self, n_clusters=8, eigen_solver=None, random_state=None,
                 n_init=10, gamma=1.0, affinity="rbf", n_neighbors=10,
                 eigen_tol=0.0, assign_labels="kmeans", degree=3, coef0=1,
                 kernel_params=None, n_jobs=1, n_components=100,
                 persist_embedding=False, kmeans_params=None):
        self.n_clusters = n_clusters
        self.eigen_solver = eigen_solver
        self.random_state = random_state
        self.n_init = n_init
        self.gamma = gamma
        self.affinity = affinity
        self.n_neighbors = n_neighbors
        self.eigen_tol = eigen_tol
        self.assign_labels = assign_labels
        self.degree = degree
        self.coef0 = coef0
        self.kernel_params = kernel_params
        self.n_jobs = n_jobs
        self.n_components = n_components
        self.persist_embedding = persist_embedding
        self.kmeans_params = kmeans_params

    def _validate(self):
        if self.assign_labels != "kmeans":
            raise ValueError("only assign_labels='kmeans' is supported")
        # honest parameter surface: params the TSQR/Nyström formulation
        # cannot honor RAISE instead of silently no-oping
        if self.eigen_solver not in (None, "tsqr"):
            raise ValueError(
                f"eigen_solver={self.eigen_solver!r} is not supported: the "
                "embedding is computed by an exact distributed TSQR SVD "
                "(pass None or 'tsqr')"
            )
        if self.eigen_tol not in (0.0, 0, "auto"):
            raise ValueError(
                "eigen_tol is not supported: the TSQR SVD is exact, not "
                "iterative (pass 0.0 or 'auto')"
            )
        if self.affinity == "nearest_neighbors":
            raise ValueError(
                "affinity='nearest_neighbors' (and hence n_neighbors) is "
                "not supported; use 'rbf', 'polynomial', 'sigmoid', "
                "'linear', or a callable"
            )
        if not callable(self.affinity) and self.affinity not in (
                "rbf", "polynomial", "sigmoid", "linear"):
            raise ValueError(f"Unknown affinity {self.affinity!r}")

    def fit(self, X, y=None):
        # one root; its children (fit.prep, fit.solve, fit.assign,
        # fit.finish) are flat: the restarts' KMeans fits open no span
        with span("fit", component="SpectralClustering",
                  n_clusters=self.n_clusters) as root:
            return self._fit(X, root)

    def _fit(self, X, root):
        k = int(self.n_clusters)
        with span("fit.prep"):
            self._validate()
            X = check_array(X, dtype=np.float32)
            n, d = X.shape
            c = min(int(self.n_components), n)
            mask = X.row_mask(X.dtype)
            base_seed = (0 if self.random_state is None
                         else int(self.random_state))
        root.add(n_rows=n, n_landmarks=c)
        # the landmark draw runs inside the program: its path is counted
        # here, static from the shapes, where the Python runs every fit
        landmark_draw = top_l_path(X.data.shape[0], c)
        with span("fit.solve", embed="tsqr",
                  landmark_draw=landmark_draw) as sp:
            emb, s, idx, fell_back = _embed(
                X.data, mask, np.uint32(base_seed % 2**32), c=c, k=k,
                mesh=X.mesh, affinity=self.affinity,
                gamma=None if self.gamma is None else float(self.gamma),
                degree=self.degree,
                coef0=self.coef0,
                kernel_params=tuple(sorted(self.kernel_params.items()))
                if self.kernel_params else None)
            # where the host waits for the embedding
            sp.sync(emb)
        embedding = ShardedArray(emb, n, X.mesh)

        with span("fit.assign") as sp:
            # n_init restarts of the assignment KMeans (sklearn semantics:
            # keep the run with the lowest inertia). Restart seeds derive
            # from the RESOLVED r=0 seed (which may come from
            # kmeans_params) so no restart duplicates it
            km_params = dict(self.kmeans_params or {})
            seed0 = km_params.pop("random_state", base_seed)
            seed0 = 0 if seed0 is None else int(seed0)
            n_init = max(int(self.n_init), 1)
            fits = []
            for r in range(n_init):
                km = KMeans(n_clusters=k, random_state=seed0 + r, **km_params)
                fits.append(km._fit_inner(embedding))
            inertias = [km.inertia_ for km in fits]
            n_iters = [km.n_iter_ for km in fits]
            winner = int(np.argmin(inertias))
            km = fits[winner]
            # every restart draws and weighs at the same shapes, so by the
            # same paths
            sp.add(restarts=n_init, n_iters=n_iters, inertias=inertias,
                   winner=winner, n_iter=sum(n_iters),
                   draws=sum(f.solver_info_["init_draw"]["draws"]
                             for f in fits),
                   draw=km.solver_info_["init_draw"]["draw"],
                   weight_passes=sum(
                       f.solver_info_["init_weights"]["weight_passes"]
                       for f in fits),
                   weights=km.solver_info_["init_weights"]["weights"],
                   assign_layout=km.solver_info_["lloyd_layout"])
        root.add(n_iter=sum(n_iters))
        with span("fit.finish") as sp:
            s_h, idx_h, fb_h = _fetch(s, idx, fell_back)
            sp.add(qr_fallbacks=int(fb_h))
            self.assign_labels_ = km
            self.labels_ = km.labels_
            self.eigenvalues_ = s_h.astype(np.float64)
            self.landmarks_ = np.asarray(idx_h, np.int64)
            self.fit_dtype_ = "float32"
            self.solver_info_ = {
                "embed": "tsqr", "precision": "float32/highest",
                "qr_fallbacks": int(fb_h), "restarts": n_init,
                "lloyd_iters": sum(n_iters), "n_iters": n_iters,
                "inertias": inertias, "winner": winner,
                "assign_fused": bool(km.solver_info_["fused"]),
                "assign_layout": km.solver_info_["lloyd_layout"],
                "landmark_draw": landmark_draw,
            }
            if self.persist_embedding:
                # reference persists the embedding in cluster memory; the
                # analog here is keeping the device-resident ShardedArray
                # on the fitted estimator instead of letting it free
                self.embedding_ = embedding
            elif hasattr(self, "embedding_"):
                del self.embedding_
            self.n_features_in_ = d
            return self

    def fit_predict(self, X, y=None):
        """``fit(X).labels_``: the device ``ShardedArray`` (see the class's
        docstring), not a host array."""
        return self.fit(X).labels_
