"""Generalized linear models: LinearRegression, LogisticRegression,
PoissonRegression.

Reference equivalent: ``dask_ml/linear_model/glm.py`` (SURVEY.md §2a GLMs
row; §3.2 call stack) — sklearn-style wrappers dispatching to dask-glm
solvers, with ``fit_intercept`` via an appended ones column and predict as
blocked matvec. Same surface here; the solvers are the device-resident jax
programs in ``solvers/solvers.py``. The intercept is the last entry of beta
everywhere; whether it also costs X a column is the solver's affair
(``_GLMBase._intercept_form``: lbfgs / gradient_descent / proximal_grad /
admm add it to eta as a scalar and leave X as wide as its features).

Regularization scaling: the objective is ``mean-NLL + lam * r(coef)`` with
``lam = 1 / (C * n_samples)`` and the intercept unpenalized, matching
sklearn's objective so the §4 parity contract holds. (dask-glm used
``lamduh = 1/C`` against a sum-NLL and penalized the intercept — a known
non-parity we deliberately fix.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..base import BaseEstimator, to_host
from ..observability import current_span, span, track_program
from ..parallel.mesh import data_shards, resolve_mesh
from ..parallel.sharded import ShardedArray
from ..utils.validation import check_X_y, check_array, check_is_fitted
from .solvers import regularizers
from .solvers.solvers import SCALAR_INTERCEPT_SOLVERS, solve


def _check_poisson_targets(ymin):
    """Shared non-negativity gate for BOTH Poisson fit paths (device-
    resident and streamed) — one rule, one message."""
    if ymin < 0:
        raise ValueError(
            "PoissonRegression requires non-negative targets; "
            f"got min(y) = {ymin}"
        )


def add_intercept(X):
    """Append a ones column (ref: dask_ml/linear_model/utils.py::add_intercept).

    Accepts a ShardedArray (ones are zeroed on padding rows so reductions
    stay exact) or any 2-D array.
    """
    if isinstance(X, ShardedArray):
        ones = X.row_mask(dtype=X.data.dtype)[:, None]
        return ShardedArray(
            jnp.concatenate([X.data, ones], axis=1), X.n_rows, X.mesh
        )
    arr = np.asarray(X)
    return np.concatenate([arr, np.ones((arr.shape[0], 1), arr.dtype)], axis=1)


from functools import partial as _partial


@jax.jit
def _matvec_eta(data, coef, intercept):
    """Decision values, the matvec every link below starts from (a jit of
    its own only so that it can be lowered alone; ``_decision_link``
    inlines it)."""
    return data @ coef.astype(data.dtype) + intercept.astype(data.dtype)


# The pointwise tail of each kind of linear prediction: decision values in,
# what the method returns a row out. Kinds by the serving plane's names —
# ``wrappers._linear_core`` compiles these same functions at a serving
# batch, ``_decision_link`` over a whole resident X: one formula each.
LINK_TAILS = {
    "margin": lambda eta: eta,
    "proba": jax.nn.sigmoid,             # P(y == classes_[1])
    "classify": lambda eta: eta > 0,     # the binary class choice
    "poisson": jnp.exp,
}

_LANES = 128


def _lane_rows(v, quantum):
    """``(n,)`` -> ``(n' / 128, 128)``: rows go 128 to a sublane row, n
    rounded up to ``quantum`` (128 a row shard, so that no shard's rows
    split; the tail is padding). Every link leaves the device in this
    lane-dense 2-D form, never as ``(n, 1)`` or ``(n, 2)``: on a TPU those
    are tiled ``T(8, 128)``, 128 / 64 times their numbers' bytes."""
    return jnp.pad(v, (0, -v.shape[0] % quantum)).reshape(-1, _LANES)


def _twice(v):
    """``(r, 128)`` -> ``(r, 256)`` with lane i at lanes 2i and 2i + 1 —
    row-major, two adjacent entries a row of X — by ONE product with a
    constant 0 / 1 matrix: the MXU is the lane shuffle XLA does not have.
    Exact for f32 at ``HIGHEST`` (the three bf16 pieces of an entry times
    1, summed in f32), and for 0 / 1 in bf16."""
    row = jax.lax.broadcasted_iota(jnp.int32, (_LANES, 2 * _LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (_LANES, 2 * _LANES), 1)
    return jnp.dot(v, (col // 2 == row).astype(v.dtype),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _odd_lanes():
    return jax.lax.broadcasted_iota(jnp.int32, (2 * _LANES,), 0) % 2 == 1


def _proba_pairs(p1, quantum):
    """``(n,)`` p1 -> ``(n' / 128, 256)``, the row-major ``(n', 2)`` matrix
    ``[1 - p1, p1]``: each entry f32 ``1 - p1`` or ``p1`` to the bit. A NaN
    row would reach its 127 neighbours through the zeros of ``_twice``'s
    product: it travels as 2.0, which no sigmoid returns, and is put back
    after."""
    both = _twice(_lane_rows(jnp.where(jnp.isnan(p1), 2.0, p1), quantum))
    pairs = jnp.where(_odd_lanes(), both, 1.0 - both)
    return jnp.where(both == 2.0, jnp.nan, pairs)


def _class_words(pick, words, quantum):
    """``(n,)`` bool -> ``(n' / 128, 128 k)``: the chosen class's ``k``
    machine words a row, row-major; ``words`` is ``(2, k)`` unsigned
    (``_label_carrier``), k = 2 for an 8-byte ``classes_.dtype``."""
    pick = _lane_rows(pick, quantum)
    if words.shape[1] == 1:
        return jnp.where(pick, words[1, 0], words[0, 0])
    pick = _twice(pick.astype(jnp.bfloat16)) > 0.5
    low, high = (jnp.where(_odd_lanes(), w[1], w[0]) for w in words)
    return jnp.where(pick, high, low)


@track_program("glm.decision")
@_partial(jax.jit, static_argnames=("link", "quantum"))
def _decision_link(data, beta, words, link, quantum):
    """Decision values AND the link in one program over a resident X: what
    a predict fetches is what it returns, lane-dense (``_lane_rows``).
    ``beta`` is ``(d + 1,)`` with the intercept last, ``words`` the two
    class values as machine words (``_label_carrier``; None for the other
    links): operands, so a new fit compiles nothing. Links (static):
    ``identity`` eta; ``exp`` the Poisson mean; ``proba2`` ``[1 - p1, p1]``
    a row (``_proba_pairs``); ``label`` / ``label_proba`` the class chosen
    by ``eta > 0`` (SGDClassifier) / ``sigmoid(eta) > 0.5``
    (LogisticRegression) — they differ for |eta| under ~1e-7 and each
    estimator keeps its own."""
    eta = _matvec_eta(data, beta[:-1], beta[-1])
    if link == "identity":
        return _lane_rows(LINK_TAILS["margin"](eta), quantum)
    if link == "exp":
        return _lane_rows(LINK_TAILS["poisson"](eta), quantum)
    if link == "proba2":
        return _proba_pairs(LINK_TAILS["proba"](eta), quantum)
    if link == "label":
        pick = LINK_TAILS["classify"](eta)
    elif link == "label_proba":
        pick = LINK_TAILS["proba"](eta) > 0.5
    else:
        raise ValueError(f"unknown link {link!r}")
    return _class_words(pick, words, quantum)


_LABEL_LINKS = ("label", "label_proba")


def _label_carrier(classes):
    """``(words, mapped)`` for the two ``classes_``. Numeric classes of 1,
    2, 4 or 8 bytes cross as their own bytes — ``(2, k)`` unsigned words in
    memory order, k = 2 for 8 bytes — so the host VIEWS what it fetched as
    ``classes_.dtype``: exact for every value, no pass. Anything else
    (strings, objects) crosses as the index 0 / 1 in one byte, which the
    host maps through ``classes_``."""
    classes = np.asarray(classes)
    if classes.dtype.kind in "biuf" and classes.dtype.itemsize <= 8:
        word = np.dtype(f"u{min(classes.dtype.itemsize, 4)}")
        return np.ascontiguousarray(classes).view(word).reshape(2, -1), False
    return np.arange(2, dtype=np.uint8).reshape(2, 1), True


def link_fetch(X, beta, link, classes=None):
    """The device half of one prediction over a resident ``X``: ONE
    dispatch of ``glm.decision`` and ONE fetch of its (row-padded, 2-D)
    result. The wait lands on the open span (``predict.decision``) as
    ``sync_s`` and the read in its ledger (``fetch_bytes``, ``fetch_s``),
    with the engagement record ``link="device"``. ``classes`` (the estimator's ``classes_``) matters to
    a label link alone."""
    sp = current_span()
    words = _label_carrier(classes)[0] if link in _LABEL_LINKS else None
    out = _decision_link(X.data, beta, words, link=link,
                         quantum=_LANES * data_shards(X.mesh))
    sp.add(link="device")
    return to_host(sp.sync(out))


def link_finish(host, n_rows, link, classes=None):
    """The host half: views of what ``link_fetch`` brought (row-major, so
    flat again, as ``classes_.dtype`` or two to a row, cut to ``n_rows``)
    and a pass over the rows only where an index had to cross: the lookup
    in ``classes_``."""
    if link == "proba2":
        return host.reshape(-1, 2)[:n_rows]
    host = host.reshape(-1)
    if link not in _LABEL_LINKS:
        return host[:n_rows]
    classes = np.asarray(classes)
    if _label_carrier(classes)[1]:
        return classes[host[:n_rows]]
    return host.view(classes.dtype)[:n_rows]


def predict_resident(est, X, link):
    """One predict over a device-resident X, in the spans every linear
    estimator shares: root ``predict`` > ``predict.decision`` (the
    estimator's ``_decision``: placement checks, dispatch, fetch) and
    ``predict.host`` (``link_finish``, the remainder)."""
    with span("predict", component=type(est).__name__) as root:
        with span("predict.decision"):
            X, host = est._decision(X, link)
        root.add(n_rows=X.n_rows)
        with span("predict.host"):
            return link_finish(host, X.n_rows, link,
                               getattr(est, "classes_", None))


@track_program("glm.grid_score")
@_partial(jax.jit, static_argnames=("n_folds",))
def _grid_hits(data, y_enc, mask, fold_id, B, n_folds):
    """Right answers of every block of a fold-stacked C grid in ONE
    program over the resident float32 X: ``(2, blocks)`` int32, the
    block's hits on its fold's test rows and on its training rows. Block
    ``j = f * k + c`` (``solvers._lam_grid_body``) is scored where
    ``fold_id == f``; its answer is ``LogisticRegression.predict``'s —
    ``sigmoid(eta) > 0.5`` picks ``classes_[1]`` — at predict's
    precision, float32 products (``HIGHEST``: the ``(blocks, d)`` stack
    rides the MXU, where one row's matvec is f32 multiplies); ``y_enc``
    is ``glm.prepare``'s 0 / 1 encoding of y."""
    d = data.shape[1]
    eta = jax.lax.dot_general(
        B[:, :d], data, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                                          # (m, n)
    if B.shape[1] > d:
        eta = eta + B[:, d:]
    right = (LINK_TAILS["proba"](eta) > 0.5) == (y_enc > 0.5)[None, :]
    fold = jnp.arange(B.shape[0]) // (B.shape[0] // n_folds)
    rows = (mask > 0)[None, :]
    test = (fold_id[None, :] == fold[:, None]) & rows
    return jnp.stack([jnp.sum(right & test, axis=1, dtype=jnp.int32),
                      jnp.sum(right & rows & ~test, axis=1,
                              dtype=jnp.int32)])


def grid_hits(prep, B, fold_id, n_folds):
    """Host ``(2, blocks)`` hits of ``_grid_hits`` for the rows ``B`` of a
    fold-stacked solve over ``prep`` (``_GLMBase._grid_prepare``): one
    dispatch and one fetch, the wait on the open span."""
    out = _grid_hits(prep.X.data, prep.y_data, prep.mask, fold_id,
                     np.asarray(B, np.float32), n_folds=n_folds)
    return to_host(current_span().sync(out))


@jax.jit
def _matvec_eta_multi(data, coef, intercept):
    """(n, C) decision values against stacked OvR coefficients (C, d)."""
    return data @ coef.T.astype(data.dtype) + intercept.astype(data.dtype)


@jax.jit
def _onehot_targets(yd, mask, classes_d):
    """(C, n) one-vs-rest targets in one program (module-level jit: a
    per-fit lambda would retrace+recompile every fit). The encoding
    invariant itself lives in solvers/streamed.py::onehot_targets,
    shared with the streamed block kernels."""
    from .solvers.streamed import onehot_targets

    return onehot_targets(yd, mask, classes_d)


@jax.jit
def _append_intercept(Xd, mask):
    """The ones column (zero on padding rows) as the last column of X,
    for the paths that take the intercept that way."""
    return jnp.concatenate([Xd, mask[:, None].astype(Xd.dtype)], axis=1)


@track_program("glm.prepare")
@_partial(jax.jit, static_argnames=("fit_intercept", "to_bf16", "encode"))
def _prepare_fit(Xd, yd, mask, fit_intercept, to_bf16, encode):
    """ONE program for all fit prep: bf16 cast, binary label scan +
    encoding — one launch and one pass over X instead of an eager chain
    (cast, scan, eq, mul) that materializes an X-sized temporary per op.

    ``fit_intercept`` appends the ones COLUMN, and is passed true only
    for a path whose mathematics index it (Newton, the C grid; see
    ``_GLMBase._intercept_form``). It is not free: on a TPU a 257-wide
    bf16 design is laid out column-major, so the column costs a
    transpose and a pad of X here and a transpose back in front of a
    Pallas kernel. Without it prep is the cast alone and X keeps its
    width. A caller whose X needs neither passes ``Xd=None`` and keeps
    its own array: a jit hands an unchanged input back as a fresh copy."""
    if fit_intercept:
        Xd = _append_intercept(Xd, mask)
    if to_bf16:
        Xd = Xd.astype(jnp.bfloat16)
    if encode:
        valid = mask > 0
        big = jnp.asarray(jnp.inf, yd.dtype)
        mn = jnp.min(jnp.where(valid, yd, big))
        mx = jnp.max(jnp.where(valid, yd, -big))
        binary = jnp.all(~valid | (yd == mn) | (yd == mx))
        y_enc = (yd == mx).astype(jnp.float32) * mask
        packed = jnp.stack([mn, mx, binary.astype(yd.dtype)])
    else:
        y_enc = yd
        packed = jnp.zeros((3,), yd.dtype)
    return Xd, y_enc, packed


class _GLMBase(BaseEstimator):
    family: str = None  # overridden per subclass

    def __init__(self, penalty="l2", dual=False, tol=1e-4, C=1.0,
                 fit_intercept=True, intercept_scaling=1.0, class_weight=None,
                 random_state=None, solver="admm", max_iter=100,
                 multi_class="ovr", verbose=0, warm_start=False, n_jobs=1,
                 solver_kwargs=None, fit_dtype=None):
        self.penalty = penalty
        self.dual = dual
        self.tol = tol
        self.C = C
        self.fit_intercept = fit_intercept
        self.intercept_scaling = intercept_scaling
        self.class_weight = class_weight
        self.random_state = random_state
        self.solver = solver
        self.max_iter = max_iter
        self.multi_class = multi_class
        self.verbose = verbose
        self.warm_start = warm_start
        self.n_jobs = n_jobs
        self.solver_kwargs = solver_kwargs
        # per-estimator precision override: None follows config.dtype
        # ("auto" = bf16 on TPU for the smooth solvers, f32 elsewhere);
        # "float32" opts out, "bfloat16" forces on. Resolved choice is
        # recorded as fit_dtype_ and in solver_info_ for streamed fits.
        self.fit_dtype = fit_dtype

    # -- internals --------------------------------------------------------
    def _encode_y_host(self, y):
        return np.asarray(y, np.float32), None

    # hooks a family must provide when its _encode_y_host returns >2
    # classes (today: logistic only) — base fits must fail with a clear
    # contract, not an AttributeError deep in _fit_streamed
    def _warm_B0(self, C, d):
        raise NotImplementedError(
            f"{type(self).__name__} does not support multiclass targets"
        )

    def _finish_fit_multi(self, beta, classes, info, n_features):
        raise NotImplementedError(
            f"{type(self).__name__} does not support multiclass targets"
        )

    def _fit_C_grid_multiclass(self, X, y, data, mask, Cs):
        """Multiclass arm of the C-grid fast path; only the logistic
        family overrides it (other families have no multiclass fit)."""
        return None

    def _run_C_grid(self, X, Cs, solve_fn, finish, form, **log_fields):
        """Shared tail of BOTH one-fold C-grid arms: one logged stacked
        solve, then fitted clones in ``Cs`` order (``_grid_fitted``).
        ``solve_fn() -> (B, info)``; ``finish(est, B_i, info)`` publishes
        one candidate's result; ``form`` is where the program kept the
        intercept (``_intercept_form``)."""
        from ..observability import fit_logger

        with span("fit", component=type(self).__name__, solver=self.solver,
                  n_rows=X.n_rows, lam_grid=len(Cs)) as sp, \
                fit_logger(type(self).__name__, solver=self.solver,
                           n_rows=X.n_rows, lam_grid=len(Cs),
                           **log_fields) as logger:
            B, info = solve_fn()
            sp.add(n_iter=info.get("n_iter"), n_evals=info.get("n_evals"))
            if logger is not None:
                logger.log(step=info.get("n_iter"), summary=True,
                           **{k: v for k, v in info.items()
                              if isinstance(v, (int, float))})
        info["intercept"] = form
        return self._grid_fitted(Cs, B, info, finish)

    def _grid_fitted(self, Cs, B, info, finish):
        """Fitted clones in ``Cs`` order from the rows ``B`` of a stacked
        solve, ``finish(est, B_i, info_i)`` publishing each."""
        from ..base import clone
        from ..config import mxu_dtype

        B = np.asarray(B, np.float64)
        per_cand = info.get("n_iter_per_candidate")
        # the C-grid design was prepared under the same rule as the
        # plain lbfgs fit (to_bf16 = resolved mxu dtype; the fast path
        # is lbfgs-only) — every fitted clone records the precision it
        # actually trained at
        dt_label = "bfloat16" if mxu_dtype(self.fit_dtype) is not None \
            else "float32"
        fitted = []
        for i, c in enumerate(Cs):
            est = clone(self).set_params(C=c)
            est.fit_dtype_ = dt_label
            # the stacked solve shares one iteration budget; publish
            # each clone's OWN convergence point (last iteration its
            # per-block gradient norm exceeded tol) as its n_iter_ —
            # the joint budget stays readable as
            # max(solver_info_["n_iter_per_candidate"])
            info_i = dict(info)
            if per_cand is not None:
                info_i["n_iter"] = int(per_cand[i])
            # a sparse fold the fast path densified under the byte
            # budget is on record, not silent (ISSUE 14 satellite):
            # every clone's solver_info_ names the fallback so reports
            # can tell a direct dense solve from the streamed path
            reason = getattr(self, "_c_grid_sparse_reason", None)
            if reason is not None:
                info_i.setdefault("sparse_stream", False)
                info_i.setdefault("sparse_stream_reason", reason)
            finish(est, B[i], info_i)
            fitted.append(est)
        return fitted

    def _dense_search_solve(self, X):
        """One-shot densify of a sparse fold for the stacked C-grid/OvR
        direct solve, behind the SAME byte budget that guards
        ``to_sharded_dense`` — an over-budget corpus raises the typed
        :class:`DenseBudgetExceeded` (the fast path bails and the
        search keeps streamed per-candidate fits) instead of silently
        allocating the dense matrix."""
        from ..config import get_config
        from ..feature_extraction.text import DenseBudgetExceeded

        n, d = int(X.shape[0]), int(X.shape[1])
        nbytes = 4 * n * d
        budget = int(get_config().to_dense_byte_budget)
        if budget > 0 and nbytes > budget:
            raise DenseBudgetExceeded(
                f"the stacked C-grid/OvR search solve would densify a "
                f"{n} x {d} sparse fold ({nbytes >> 20} MiB > "
                f"config.to_dense_byte_budget {budget >> 20} MiB); "
                "falling back to streamed per-candidate fits"
            )
        # _csr_dense casts the nnz VALUES to f32 before toarray(), so
        # the transient is the one budgeted dense block — a f64 source
        # densified first would peak at ~3x the budget this guard
        # enforces
        from ..parallel.streaming import _csr_dense

        return _csr_dense(X.tocsr(), 0, n, np.float32)

    def _check_unsupported(self):
        """Honest-raise for accepted-but-unimplemented params (same
        policy as SpectralClustering's): silently ignoring
        class_weight="balanced" would return unweighted fits that LOOK
        like weighted ones. The reference wrapper ignores it silently —
        a non-parity we fix on purpose."""
        if self.class_weight is not None:
            raise ValueError(
                "class_weight is not supported; reweight via "
                "sample-level resampling, or leave class_weight=None"
            )

    def _penalty_setup(self, d, n_rows):
        """(pmask, lam): intercept unpenalized, sklearn's 1/(C*n) scaling
        — the ONE place the regularization bookkeeping lives (shared by
        the resident, streamed, and multiclass fit paths)."""
        pmask = np.ones(d, np.float32)
        if self.fit_intercept:
            pmask[-1] = 0.0
        lam = 1.0 / (self.C * n_rows) if self.penalty != "none" else 0.0
        return pmask, lam

    def _intercept_form(self, stacked=False):
        """Where a resident fit keeps the intercept, recorded as
        ``solver_info_["intercept"]``: ``"scalar"`` — the last entry of
        beta, added to eta by the loss, X as wide as the features (every
        solver that touches X through ``_select_loss`` alone, and ADMM,
        whose local Newton step borders its Hessian itself);
        ``"column"`` — a ones column appended to X (Newton, whose
        Hessian indexes it; the ``stacked`` one-vs-rest programs, the
        C grid's among them); ``"none"``. The binary C grid takes the
        scalar form (``solvers._lam_grid_body``)."""
        if not self.fit_intercept:
            return "none"
        scalar = not stacked and self.solver in SCALAR_INTERCEPT_SOLVERS
        return "scalar" if scalar else "column"

    def _warm_beta0(self, d, xp):
        """Shape-guarded warm start: a stale coef_ from a DIFFERENT
        problem shape (e.g. a prior multiclass fit) must not leak into
        this solve — silently starting from a malformed vector crashes
        deep in the jitted loss."""
        if self.warm_start and getattr(self, "coef_", None) is not None:
            single = np.ndim(self.coef_) == 1 or np.shape(self.coef_)[0] == 1
            flat = self._coef_flat()
            if single and flat.shape[0] == d - int(self.fit_intercept):
                b = (np.r_[flat, np.ravel(self.intercept_)[:1]]
                     if self.fit_intercept else flat)
                return xp.asarray(b, dtype=np.float32)
        return xp.zeros(d, np.float32)

    def _finish_fit(self, beta, classes, info, n_features):
        beta = np.asarray(beta, np.float64)
        if self.fit_intercept:
            self.intercept_ = beta[-1]
            coef = beta[:-1]
        else:
            self.intercept_ = 0.0
            coef = beta
        self._set_coef(coef, classes)
        self.n_iter_ = info.get("n_iter")
        self.solver_info_ = info
        if "fit_dtype" in info:  # streamed fits resolve it in the solver
            self.fit_dtype_ = info["fit_dtype"]
        self.n_features_in_ = n_features
        return self

    def _fit_streamed(self, X, y, block_rows):
        """Out-of-core fit: X stays host-resident (np.memmap or large
        ndarray); blocks stream through prefetched device_put into
        per-block loss/grad/Hessian kernels (solvers/streamed.py). The
        reference's analog is dask-glm over host-backed chunks
        (SURVEY.md §3.2); here the optimizer state is the only host-side
        math. y is encoded to a host float32 vector (1/d the size of X).

        Under a live multi-process runtime (``jax.distributed``), X/y are
        the PROCESS-LOCAL shard (per-host memmaps, SURVEY §1 L2 dd
        partitions): per-pass block sums psum across processes, n_rows
        and the class set are global, and every process converges to the
        identical global fit."""
        if self.penalty not in regularizers.KNOWN:
            raise ValueError(f"Unknown penalty {self.penalty!r}")
        from ..parallel import distributed as dist
        from ..parallel.streaming import BlockStream
        from ..observability import fit_logger
        from .solvers.streamed import solve_streamed

        multi_host = dist.process_count() > 1
        reduce = dist.psum_host if multi_host else None
        y_host, classes = self._encode_y_host(y)
        n, d_feat = X.shape[0], X.shape[1]
        if multi_host:
            n = int(dist.psum_host(np.asarray(float(n))))
        d = d_feat + (1 if self.fit_intercept else 0)
        pmask, lam = self._penalty_setup(d, n)
        stream = BlockStream((X, y_host), block_rows=block_rows)
        kwargs = dict(self.solver_kwargs or {})
        l1_ratio = kwargs.pop("l1_ratio", 0.5)
        # pass-granular checkpoint/auto-resume (ISSUE 11): the solver
        # saves its host state each outer iteration under a fingerprint
        # token and clears on completion; None (knobs off, multi-host,
        # warm start) leaves the fit exactly as before
        ckpt = None
        if not (multi_host or getattr(self, "warm_start", False)):
            from ..reliability.stream_ckpt import stream_checkpoint

            ckpt = stream_checkpoint(
                "glm",
                (type(self).__name__, self.solver, self.penalty,
                 getattr(self, "C", None), float(np.asarray(lam)),
                 l1_ratio, self.fit_intercept, self.max_iter, self.tol,
                 self.family, repr(sorted(kwargs.items())), n, d,
                 int(stream.block_rows),
                 None if classes is None
                 else tuple(np.asarray(classes).tolist())),
                arrays=(X, y_host),
            )
        if classes is not None and len(classes) > 2:
            # one-vs-rest out-of-core: y_host carries class CODES; every
            # epoch streams X once for all C classes
            from .solvers.streamed import solve_streamed_multi

            C = len(classes)
            B0 = self._warm_B0(C, d)
            with span("fit", component=type(self).__name__,
                      solver=self.solver, streamed=True, n_rows=n,
                      n_classes=C) as sp, \
                    fit_logger(type(self).__name__, solver=self.solver,
                               streamed=True, n_rows=n,
                               n_classes=C) as logger:
                Beta, info = solve_streamed_multi(
                    self.solver, stream, n, B0, self.family, self.penalty,
                    lam, pmask, l1_ratio=l1_ratio,
                    intercept=self.fit_intercept, max_iter=self.max_iter,
                    tol=self.tol, logger=logger, reduce=reduce,
                    fit_dtype=self.fit_dtype, ckpt=ckpt, **kwargs,
                )
                sp.add(n_iter=info.get("n_iter"),
                       data_passes=info.get("data_passes"))
            self.training_profile_ = stream.profile_snapshot()
            self._last_stream_stats = getattr(stream, "stats", None)
            return self._finish_fit_multi(Beta, classes, info, d_feat)
        beta0 = self._warm_beta0(d, np)
        with span("fit", component=type(self).__name__, solver=self.solver,
                  streamed=True, n_rows=n) as sp, \
                fit_logger(type(self).__name__, solver=self.solver,
                           streamed=True, n_rows=n) as logger:
            beta, info = solve_streamed(
                self.solver, stream, n, beta0, self.family, self.penalty,
                lam, pmask, l1_ratio=l1_ratio, intercept=self.fit_intercept,
                max_iter=self.max_iter, tol=self.tol, logger=logger,
                reduce=reduce, fit_dtype=self.fit_dtype, ckpt=ckpt,
                **kwargs,
            )
            sp.add(n_iter=info.get("n_iter"),
                   data_passes=info.get("data_passes"))
        # per-feature training profile for train-vs-serve drift scoring
        self.training_profile_ = stream.profile_snapshot()
        # the last pass's staging stats (layout, K, dispatches, native
        # reader) — same attribute the streamed SGD fits publish
        self._last_stream_stats = getattr(stream, "stats", None)
        return self._finish_fit(beta, classes, info, d_feat)

    def _grid_eligible(self):
        """Whether this estimator's C grid can run as the stacked lbfgs
        program. class_weight != None is an ELIGIBILITY bail, not a
        raise: the caller's general path re-runs est.fit(), which raises
        the clean unsupported-param error instead of a fast-path
        warning."""
        return (self.solver == "lbfgs" and self.penalty in ("l2", "none")
                and not self.solver_kwargs and not self.warm_start
                and self.class_weight is None)

    def _grid_prepare(self, X, y, binary_only=False):
        """ONE ``check_X_y`` and ONE cast of ``X`` for a stacked C grid,
        over every fold it will train: the design in the fit dtype at X's
        own width (the intercept is each block's last beta entry,
        ``_lam_grid_body``), after the label scan (``glm.prepare`` of the
        labels alone) has encoded the labels. Returns a namespace (``X``,
        ``y``, ``data``, ``y_data``, ``mask``, ``classes``, ``multiclass``,
        ``fit_dtype``), or None where X streams or a sparse X is over the
        densify budget. ``binary_only``: a target of more than two classes
        returns with ``data`` None, X untouched."""
        import types

        from ..config import mxu_dtype
        from ..parallel.streaming import _is_sparse_source, stream_plan

        self._c_grid_sparse_reason = None
        if _is_sparse_source(X):
            # stacked direct solves need the dense design ONCE; the
            # densify rides the to_sharded_dense byte budget — typed
            # refusal (fast path bails, streamed per-candidate fits
            # carry the search) instead of a silent n x d allocation,
            # and a within-budget densify is recorded in every clone's
            # solver_info_ as sparse_stream_reason="search-dense-solve"
            from ..feature_extraction.text import DenseBudgetExceeded

            try:
                X = self._dense_search_solve(X)
            except DenseBudgetExceeded:
                return None
            self._c_grid_sparse_reason = "search-dense-solve"
        elif stream_plan(X) is not None:
            return None
        mesh = resolve_mesh(getattr(X, "mesh", None))
        X, y = check_X_y(X, y, mesh=mesh, dtype=np.float32)
        mask = X.row_mask(dtype=jnp.float32)
        to_bf16 = mxu_dtype(self.fit_dtype) is not None
        y_data, classes, multiclass = y.data, None, False
        if self.family == "logistic":
            _, y_data, packed = _prepare_fit(
                None, y.data, mask, fit_intercept=False, to_bf16=False,
                encode=True)
            pk = to_host(packed)
            # >2 classes (or one): the one-vs-rest arm
            multiclass = not bool(pk[2]) or pk[0] == pk[1]
            if not multiclass:
                classes = np.asarray(pk[:2])
        elif self.family == "poisson":
            _check_poisson_targets(
                float(jnp.min(jnp.where(mask > 0, y_data, jnp.inf)))
            )
        ns = types.SimpleNamespace(
            X=X, y=y, data=X.data, y_data=y_data, mask=mask, classes=classes,
            multiclass=multiclass,
            fit_dtype="bfloat16" if to_bf16 else "float32")
        if multiclass and binary_only:
            ns.data = None
        elif to_bf16:          # else an f32 design: X as it is
            ns.data = _prepare_fit(X.data, y_data, mask, fit_intercept=False,
                                   to_bf16=True, encode=False)[0]
        return ns

    def _grid_blocks(self, prep, Cs, n_train, fold_id=None):
        """The stacked solve of ``len(n_train) * len(Cs)`` blocks over the
        prepared design, block ``f * len(Cs) + c`` candidate ``Cs[c]`` on
        fold ``f`` (``fold_id``: ``_lam_grid_body``'s; None with one
        fold of every row). Each block's ``lam = 1 / (C * n_train_f)``
        comes from ``_penalty_setup``, the one place the regularization
        bookkeeping lives. Returns ``((blocks, d [+ 1]) float64 betas,
        info)``."""
        from ..base import clone
        from .solvers.solvers import solve_lam_grid

        p = prep.data.shape[1] + int(self.fit_intercept)
        pmask = self._penalty_setup(p, 1)[0]
        lams = [clone(self).set_params(C=c)._penalty_setup(p, nt)[1]
                for nt in n_train for c in Cs]
        B, info = solve_lam_grid(
            prep.data, prep.y_data, prep.mask, prep.X.n_rows, lams, pmask,
            self.family, self.penalty, max_iter=self.max_iter, tol=self.tol,
            fold_id=fold_id, n_train=n_train, intercept=self.fit_intercept,
        )
        return np.asarray(B, np.float64), info

    def _fit_C_grid(self, X, y, Cs):
        """Fit ``len(Cs)`` clones differing only in ``C`` as ONE
        stacked-lam L-BFGS program over a shared design matrix — the
        one-fold case of the stacked grid (GridSearchCV's homogeneous-
        trial fast path over fold copies; SURVEY.md §3.4). Returns the
        fitted clones in ``Cs`` order, or None when this fit shape isn't
        eligible (caller falls back to per-candidate fits)."""
        if not self._grid_eligible():
            return None
        prep = self._grid_prepare(X, y)
        if prep is None:
            return None
        X = prep.X
        if prep.multiclass:
            # the one-vs-rest arm keeps the intercept as a column (degenerate
            # single-class keeps None — the general path raises the clean
            # error)
            data = _append_intercept(prep.data, prep.mask) \
                if self.fit_intercept else prep.data
            return self._fit_C_grid_multiclass(X, prep.y, data, prep.mask,
                                               Cs)
        return self._run_C_grid(
            X, Cs, lambda: self._grid_blocks(prep, Cs, [X.n_rows]),
            self._grid_finish(prep.classes, X.shape[1]),
            self._intercept_form())

    def _grid_finish(self, classes, n_features):
        """``finish`` of ``_grid_fitted`` for one binary (or regression)
        block: the clone's coefficients, intercept and classes."""
        def finish(est, beta, info):
            if classes is not None:
                est.classes_ = classes
            est._finish_fit(beta, classes, info, n_features)
        return finish

    def fit(self, X, y):
        from ..parallel.streaming import stream_plan

        self._check_unsupported()
        block_rows = stream_plan(X)
        if block_rows is not None:
            return self._fit_streamed(X, y, block_rows)
        # the root span covers the whole resident call; its children
        # (fit.validate, fit.prepare, fit.solve, fit.finish) are the
        # phases, each ending where its host code ends
        with span("fit", component=type(self).__name__,
                  solver=self.solver) as root:
            return self._fit_resident(X, y, root)

    def _fit_resident(self, X, y, root):
        with span("fit.validate"):
            mesh = resolve_mesh(getattr(X, "mesh", None))
            X, y = check_X_y(X, y, mesh=mesh, dtype=np.float32)
            if self.penalty not in regularizers.KNOWN:
                raise ValueError(f"Unknown penalty {self.penalty!r}")
            # bf16 design matrix: the _smooth_loss matvec rides the MXU
            # at bf16 rate with f32 accumulation; solver state / y / mask
            # stay f32. Newton/ADMM are excluded: Newton's Hessian matmuls
            # would silently upcast (no speedup), and ADMM's local step
            # states f32 for eta and the gradient (its Gram alone runs at
            # the MXU's default, ``solvers._newton_stats``) — an f32 X is
            # read where it lies, with no copy at all
            from ..config import mxu_dtype

            use_bf16 = mxu_dtype(self.fit_dtype) is not None \
                and self.solver in ("lbfgs", "gradient_descent",
                                    "proximal_grad")
            # resolved precision on record: the auto policy's f32
            # fallback (off-TPU, or a solver whose Hessian math excludes
            # bf16) must be visible, not silent
            self.fit_dtype_ = "bfloat16" if use_bf16 else "float32"
            mask = X.row_mask(dtype=jnp.float32)
        root.add(n_rows=X.n_rows)
        form = self._intercept_form()
        with span("fit.prepare") as sp:
            touches_x = use_bf16 or form == "column"
            data, y_data, packed = _prepare_fit(
                X.data if touches_x else None, y.data, mask,
                fit_intercept=form == "column", to_bf16=use_bf16,
                encode=self.family == "logistic",
            )
            if data is None:       # an f32 scalar-form fit: X as it is
                data = X.data
            if self.family == "poisson":
                _check_poisson_targets(
                    float(jnp.min(jnp.where(mask > 0, y_data, jnp.inf)))
                )
            classes = None
            multiclass = False
            if self.family == "logistic":
                # one small fetch: (mn, mx, binary) — where the host
                # waits for the prep pass
                pk = to_host(sp.sync(packed))
                multiclass = not bool(pk[2]) or pk[0] == pk[1]
                if not multiclass:
                    classes = np.asarray(pk[:2])
                    self.classes_ = classes
        if multiclass:
            # >2 (or 1) classes: the one-vs-rest path (vmapped
            # multi-target solve; beyond the reference's binary-only
            # dask-glm logistic family). Its programs take the intercept
            # as a column, and only the label scan above could tell:
            # the column prep left out is appended here
            if form == "scalar":
                data = _append_intercept(data, mask)
            return self._fit_multiclass(X, y, data, mask, root)
        from ..observability import active_logger, fit_logger

        with span("fit.solve") as sp, \
                fit_logger(type(self).__name__, solver=self.solver,
                           n_rows=X.n_rows) as logger, active_logger(logger):
            d = data.shape[1] + (form == "scalar")     # beta's length
            pmask, lam = self._penalty_setup(d, X.n_rows)
            # beta0, lam and pmask go to the solver as HOST values: they
            # ride in with its program's dispatch (``jnp.asarray`` of each
            # is an eager launch with the chip idle)
            beta0 = self._warm_beta0(d, np)
            kwargs = dict(self.solver_kwargs or {})
            l1_ratio = kwargs.pop("l1_ratio", 0.5)
            log_steps = logger is not None
            beta, info = solve(
                self.solver,
                X=data, y=y_data, mask=mask,
                n_rows=X.n_rows, beta0=beta0, family=self.family,
                reg=self.penalty, lam=np.float32(lam), pmask=pmask,
                l1_ratio=l1_ratio,
                max_iter=self.max_iter, tol=self.tol, mesh=mesh,
                log=log_steps, intercept=form == "scalar", **kwargs,
            )
            info["intercept"] = form
            sp.add(**{k: info[k] for k in (
                "n_iter", "n_evals", "fused", "intercept", "local_steps",
                "primal_residual", "dual_residual", "rho", "nnz",
                "local_step") if k in info})
            if logger is not None and not log_steps:
                logger.log(step=info.get("n_iter"), summary=True,
                           **{k: v for k, v in info.items()
                              if isinstance(v, (int, float))})
        root.add(n_iter=info.get("n_iter"))
        with span("fit.finish"):
            return self._finish_fit(to_host(beta), classes, info, X.shape[1])

    def _coef_flat(self):
        return np.ravel(self.coef_)

    def _intercept_scalar(self) -> np.float32:
        """intercept_ as one scalar: binary LogisticRegression stores
        shape (1,), the regressions store a plain float."""
        return np.float32(np.ravel(self.intercept_)[0]
                          if np.ndim(self.intercept_) else self.intercept_)

    def _set_coef(self, coef, classes):
        self.coef_ = coef

    def _beta(self) -> np.ndarray:
        """``(d + 1,)`` float32, the intercept last: the operand of
        ``glm.decision``."""
        return np.append(np.asarray(self._coef_flat(), np.float32),
                         self._intercept_scalar())

    def _eta_host(self, X):
        """Decision values as a host (n,) array. An out-of-core X streams
        block-wise through the matvec and is assembled on the host; a
        resident X runs ``glm.decision`` with the ``identity`` link and is
        fetched once (the wait is the open span's ``sync_s``)."""
        from ..parallel.streaming import stream_plan, streamed_map

        block_rows = stream_plan(X)
        if block_rows is not None:
            coef = jnp.asarray(self._coef_flat(), jnp.float32)
            b0 = jnp.asarray(self._intercept_scalar())
            eta = streamed_map(
                X, block_rows, lambda blk: blk.arrays[0] @ coef + b0
            )
            current_span().add(link="host")
            return eta
        X, host = self._decision(X)
        return link_finish(host, X.n_rows, "identity")

    def _decision(self, X, link="identity"):
        """The device half for a resident X: ``(X as placed, the fetched
        host array of glm.decision under link)``; ``link_finish`` is the
        host half."""
        X = check_array(X, dtype=np.float32)
        return X, link_fetch(X, self._beta(), link,
                             getattr(self, "classes_", None))


class LinearRegression(_GLMBase):
    """Ref: dask_ml/linear_model/glm.py::LinearRegression."""

    family = "normal"

    def predict(self, X):
        check_is_fitted(self, "coef_")
        return self._eta_host(X)

    def score(self, X, y):
        from ..metrics import r2_score

        return r2_score(y, self.predict(X))


class PoissonRegression(_GLMBase):
    """Ref: dask_ml/linear_model/glm.py::PoissonRegression."""

    family = "poisson"

    def _encode_y_host(self, y):
        y = np.asarray(y, np.float32)
        if y.size:
            _check_poisson_targets(float(y.min()))
        return y, None

    def predict(self, X):
        from ..parallel.streaming import stream_plan

        check_is_fitted(self, "coef_")
        if stream_plan(X) is not None:     # assembled on the host anyway
            return np.exp(self._eta_host(X))
        return predict_resident(self, X, "exp")

    def score(self, X, y):
        from ..metrics import r2_score

        return r2_score(y, self.predict(X))


class LogisticRegression(_GLMBase):
    """Ref: dask_ml/linear_model/glm.py::LogisticRegression. The
    reference (via dask-glm's logistic family) is binary-only; here >2
    classes fit one-vs-rest, with the C per-class solves stacked into a
    single XLA program for smooth solvers."""

    family = "logistic"

    def _fit_multiclass(self, X, y, data, mask, root):
        self._check_multi_class()
        classes = np.unique(y.to_numpy())
        if len(classes) < 2:
            raise ValueError(
                f"LogisticRegression needs at least 2 classes; got "
                f"{len(classes)}"
            )
        from ..observability import fit_logger
        from .solvers.solvers import solve_multi

        # (C, n) one-vs-rest targets in ONE program; padding rows zeroed
        Y = _onehot_targets(y.data, mask, jnp.asarray(classes, y.dtype))
        d = data.shape[1]
        pmask, lam = self._penalty_setup(d, X.n_rows)
        C = len(classes)
        B0 = self._warm_B0(C, d)
        kwargs = dict(self.solver_kwargs or {})
        l1_ratio = kwargs.pop("l1_ratio", 0.5)
        root.add(n_classes=C)
        with span("fit.solve") as sp, \
                fit_logger(type(self).__name__, solver=self.solver,
                           n_rows=X.n_rows, n_classes=C) as logger:
            beta, info = solve_multi(
                self.solver, X=data, Y=Y, mask=mask, n_rows=X.n_rows,
                B0=B0, family=self.family, reg=self.penalty,
                lam=np.float32(lam), pmask=pmask,
                l1_ratio=l1_ratio, max_iter=self.max_iter, tol=self.tol,
                mesh=X.mesh, **kwargs,
            )
            info["intercept"] = self._intercept_form(stacked=True)
            sp.add(n_iter=info.get("n_iter"), intercept=info["intercept"])
            root.add(n_iter=info.get("n_iter"))
            if logger is not None:
                logger.log(step=info.get("n_iter"), summary=True,
                           **{k: v for k, v in info.items()
                              if isinstance(v, (int, float))})
        return self._finish_fit_multi(to_host(beta), classes, info,
                                      X.shape[1])

    def _fit_C_grid_multiclass(self, X, y, data, mask, Cs):
        """k candidates x C one-vs-rest classes solved as ONE stacked
        program per fold (the multiclass arm of GridSearchCV's pure-C
        fast path). Returns fitted clones in ``Cs`` order, or None for
        degenerate targets (the general path raises cleanly)."""
        if self.multi_class not in ("auto", "ovr"):
            return None  # general path raises the clean error
        classes = np.unique(y.to_numpy())
        if len(classes) < 2:
            return None
        from .solvers.solvers import solve_lam_grid_multi

        from ..base import clone

        Y = _onehot_targets(y.data, mask, jnp.asarray(classes, y.dtype))
        d = data.shape[1]
        lams = [clone(self).set_params(C=c)._penalty_setup(d, X.n_rows)[1]
                for c in Cs]
        pmask = self._penalty_setup(d, X.n_rows)[0]
        return self._run_C_grid(
            X, Cs,
            lambda: solve_lam_grid_multi(
                data, Y, mask, X.n_rows, lams, pmask, self.family,
                self.penalty, max_iter=self.max_iter, tol=self.tol,
            ),
            lambda est, Bi, info: est._finish_fit_multi(
                Bi, classes, dict(info), d - int(self.fit_intercept)
            ),
            self._intercept_form(stacked=True), n_classes=len(classes),
        )

    def _check_multi_class(self):
        if self.multi_class not in ("auto", "ovr"):
            raise ValueError(
                f"multi_class={self.multi_class!r} is not supported; "
                "use 'ovr' (or 'auto')"
            )

    def _warm_B0(self, C, d):
        """(C, d) start: prior stacked OvR coefficients when warm_start
        and the shape matches THIS problem, else zeros."""
        if (self.warm_start and getattr(self, "coef_", None) is not None
                and np.shape(self.coef_)
                == (C, d - (1 if self.fit_intercept else 0))):
            return np.asarray(
                np.c_[self.coef_, np.ravel(self.intercept_)]
                if self.fit_intercept else self.coef_, np.float32,
            )
        return np.zeros((C, d), np.float32)

    def _finish_fit_multi(self, beta, classes, info, n_features):
        beta = np.asarray(beta, np.float64)
        if self.fit_intercept:
            self.intercept_ = beta[:, -1]
            self.coef_ = beta[:, :-1]
        else:
            self.intercept_ = np.zeros(len(classes))
            self.coef_ = beta
        self.classes_ = classes
        self.n_iter_ = info.get("n_iter")
        self.solver_info_ = info
        if "fit_dtype" in info:  # streamed fits resolve it in the solver
            self.fit_dtype_ = info["fit_dtype"]
        self.n_features_in_ = n_features
        return self

    def _is_multiclass(self):
        return getattr(self, "coef_", None) is not None \
            and np.ndim(self.coef_) == 2 and self.coef_.shape[0] > 1

    def _encode_y_host(self, y):
        from ..parallel import distributed as dist

        y = np.asarray(y)
        classes = np.unique(y)
        if dist.process_count() > 1:
            # multi-host streamed fit: the class set is the UNION over
            # every process's local shard (a shard missing a class must
            # not shift the others' codes)
            classes = np.unique(
                np.concatenate(dist.allgather_object(classes))
            )
        if len(classes) < 2:
            raise ValueError(
                f"LogisticRegression needs at least 2 classes; got "
                f"{len(classes)}"
            )
        if len(classes) > 2:
            self._check_multi_class()
            # class CODES 0..C-1 (float32, 1/d the bytes of X) — the
            # streamed block kernels rebuild one-hot targets on device
            self.classes_ = classes
            codes = np.searchsorted(classes, y).astype(np.float32)
            return codes, classes
        self.classes_ = classes
        return (y == classes[1]).astype(np.float32), classes

    def _set_coef(self, coef, classes):
        self.coef_ = coef.reshape(1, -1)
        self.intercept_ = np.atleast_1d(self.intercept_)

    def _eta_multi_host(self, X):
        """(n, C) decision values — one matmul program against the
        stacked OvR coefficient matrix; streams block-wise for
        out-of-core inputs exactly like the binary path."""
        from ..parallel.streaming import stream_plan, streamed_map

        coef = np.asarray(self.coef_, np.float32)
        b = np.asarray(self.intercept_, np.float32)
        block_rows = stream_plan(X)
        if block_rows is not None:
            coef_d = jnp.asarray(coef.T)
            b_d = jnp.asarray(b)
            return streamed_map(
                X, block_rows, lambda blk: blk.arrays[0] @ coef_d + b_d
            )
        X = check_array(X, dtype=np.float32)
        eta = _matvec_eta_multi(X.data, coef, b)
        return to_host(eta)[: X.n_rows]

    def decision_function(self, X):
        check_is_fitted(self, "coef_")
        if self._is_multiclass():
            return self._eta_multi_host(X)
        return self._eta_host(X)

    def _device_link_applies(self, X):
        """Where the link runs, by what the input and the fit show: on the
        device for a resident X and a binary fit. A streamed X is
        assembled block by block on the host anyway, and an ``(n, C)``
        one-vs-rest result has the lane problem of ``(n, 2)`` with no
        dense form yet: both keep the host tail and say ``link="host"``."""
        from ..parallel.streaming import stream_plan

        return not self._is_multiclass() and stream_plan(X) is None

    def predict_proba(self, X):
        check_is_fitted(self, "coef_")
        if self._device_link_applies(X):
            return predict_resident(self, X, "proba2")
        from scipy.special import expit

        with span("predict", component=type(self).__name__) as root:
            if self._is_multiclass():
                # OvR probabilities: per-class sigmoids normalized to sum
                # 1 (sklearn's OvR contract)
                with span("predict.decision", link="host"):
                    eta = self._eta_multi_host(X)
                root.add(n_rows=len(eta))
                with span("predict.host"):
                    p = expit(eta)
                    return p / np.maximum(p.sum(axis=1, keepdims=True),
                                          1e-12)
            with span("predict.decision"):
                eta = self._eta_host(X)
            root.add(n_rows=len(eta))
            with span("predict.host"):
                p1 = expit(eta)
                return np.stack([1.0 - p1, p1], axis=1)

    def predict_log_proba(self, X):
        """Log of predict_proba (sklearn API; the reference's glm lacks
        it but sklearn users expect it on a classifier)."""
        from ..base import log_proba

        return log_proba(self.predict_proba(X))

    def predict(self, X):
        check_is_fitted(self, "coef_")
        if self._device_link_applies(X):
            return predict_resident(self, X, "label_proba")
        if self._is_multiclass():
            eta = self._eta_multi_host(X)
            return self.classes_[np.argmax(eta, axis=1)]
        proba = self.predict_proba(X)
        return self.classes_[(proba[:, 1] > 0.5).astype(int)]

    def score(self, X, y):
        from ..metrics import accuracy_score

        return accuracy_score(y, self.predict(X))
