"""The plain reference for the ``hyperband`` family: Hyperband (Li et al.,
2018; dask-ml's ``HyperbandSearchCV``) over a linear model trained by
minibatch gradient steps, written from the configuration's words alone — no
code of ``dask_ml_tpu/``.

**The schedule.** ``brackets(max_iter, eta)``: with ``s_max = floor(log_eta
max_iter)`` and ``B = (s_max + 1) max_iter``, bracket ``s = s_max .. 0`` starts
``n = ceil(B / max_iter * eta^s / (s + 1))`` models at ``r = max(1, floor(
max_iter eta^-s))`` calls. Successive halving inside a bracket
(``rungs``): all ``n`` models train to ``r`` calls and are scored; the top
``max(1, floor(n / eta))`` are kept and train on to ``min(r eta, max_iter)``;
and so on until a rung at ``max_iter`` calls has been scored.

**The draw.** Bracket ``s`` draws its ``n`` candidates by
``ParameterSampler(parameters, n, random_state=random_state + s)``; model ids
run over the brackets in that order (``s_max`` first).

**The split.** ``np.random.RandomState(random_state)`` shuffles, chip by
chip in order, the row indices of that chip's shard (``arange(lo, hi)``,
legacy ``shuffle``); the first ``ceil(m * test_size)`` of a shard's ``m``
shuffled indices are held out, the rest train, in shuffled order; the chips'
parts are concatenated.

**The partition.** The training rows, in that order, are cut into ``B``
blocks of ``S`` rows (``partition``: at least ``max(chips, 8)`` blocks, ``S`` a
multiple of the chips; the last block may be short). Call ``i`` (0-based) of
a model trains block ``i mod B``; its clock at that call is ``t = i + 1``.

**The step** is ``references/sgd.py``'s, for ``N`` weight vectors at once
(each with its own ``alpha`` and learning rate): the same residual, the same
roundings by ``lax.reduce_precision`` where a precision is stated, both
products at ``highest``, the update in float32 on the host. One float32 sum
runs over a block's rows where ``references/sgd.py`` adds chunks in float64:
that reads 9e-8 from it (``tolerances_sgd.py``), far under every band here.

**The promotion rule.** A cut keeps the top ``k`` by the last recorded score;
among equal scores the LOWER model id stays (``keep``).

**The score** is accuracy on the held-out rows: ``(eta > 0) == y`` with
``eta`` the decision at the stated precision (the held-out block and the
weights of the product rounded to the design dtype) or in float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references import sgd as ref


# -- the schedule --------------------------------------------------------------

def brackets(max_iter, eta):
    """[(s, n models, r first calls)], ``s_max`` first."""
    s_max = int(math.floor(math.log(max_iter, eta)))
    B = (s_max + 1) * max_iter
    return [(s, int(math.ceil(B / max_iter * eta ** s / (s + 1))),
             max(1, int(max_iter * eta ** -s)))
            for s in range(s_max, -1, -1)]


def rungs(n, r, max_iter, eta):
    """[(models alive, calls each has made when scored)] of one bracket."""
    out = [(n, r)]
    while r < max_iter:
        n, r = max(1, n // eta), min(r * eta, max_iter)
        out.append((n, r))
    return out


def metadata(max_iter, eta):
    """Models and ``partial_fit`` calls of the whole search, and a bracket."""
    per = []
    for s, n, r in brackets(max_iter, eta):
        calls, before = 0, 0
        for alive, at in rungs(n, r, max_iter, eta):
            calls += alive * (at - before)
            before = at
        per.append({"bracket": s, "n_models": n, "partial_fit_calls": calls})
    return {"n_models": sum(b["n_models"] for b in per),
            "partial_fit_calls": sum(b["partial_fit_calls"] for b in per),
            "brackets": per}


def draw(parameters, max_iter, eta, random_state):
    """(the candidates' parameters in model-id order, each one's bracket)."""
    from sklearn.model_selection import ParameterSampler

    params, bracket_of = [], []
    for s, n, _ in brackets(max_iter, eta):
        got = list(ParameterSampler(parameters, n,
                                    random_state=random_state + s))
        params.extend(got)
        bracket_of.extend([s] * len(got))
    return params, bracket_of


def split(n_rows, chips, test_size, random_state):
    """(train row indices in training order, held-out row indices)."""
    rng = np.random.RandomState(random_state)
    per = -(-n_rows // chips)
    train, test = [], []
    for c in range(chips):
        lo, hi = min(c * per, n_rows), min((c + 1) * per, n_rows)
        if hi <= lo:
            continue
        idx = np.arange(lo, hi)
        rng.shuffle(idx)
        n_test = int(np.ceil((hi - lo) * test_size))
        test.append(idx[:n_test])
        train.append(idx[n_test:])
    return np.concatenate(train), np.concatenate(test)


def partition(n_train, chips):
    """(blocks B, rows a block S) of ``n_train`` training rows."""
    n_pad = max(-(-n_train // chips) * chips, chips)
    s = -(-n_pad // max(chips, 8))
    S = max(-(-s // chips) * chips, 1)
    return -(-n_pad // S), S


def keep(scores, k):
    """The ``k`` model ids a cut keeps of ``{model id: score}``."""
    return sorted(scores, key=lambda m: (-scores[m], m))[:k]


# -- the arithmetic --------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("loss", "design_dtype",
                                             "fit_intercept", "lower"))
def _sums(W, Xb, yb, *, loss, design_dtype=None, fit_intercept=True,
          lower=None):
    """``sum_i r_i x_i`` (N, d) and ``sum_i r_i`` (N,) of N weight vectors
    over one block: ``references/sgd.py::_chunk_sums`` with a weight axis."""
    Xb = ref._round(Xb.astype(jnp.float32), design_dtype)
    b0 = W[:, -1] if fit_intercept else jnp.zeros_like(W[:, -1])
    eta = jnp.dot(Xb, ref._round(W[:, :-1], design_dtype).T)
    if lower == "accumulate":
        eta = ref._round(eta, jnp.bfloat16)
    r = ref._residual(eta + b0[None, :],
                      yb.astype(jnp.float32)[:, None], loss)
    return jnp.dot(r.T, Xb), jnp.sum(r, axis=0)


def step_many(W, Xb, yb, lr, alpha, l2, l1, *, loss, design_dtype=None,
              fit_intercept=True, lower=None):
    """One update of the host float32 stack ``W (N, d + 1)`` (intercepts
    last) on one device block, model ``j`` at its own ``lr[j]`` and
    ``alpha[j]``: ``references/sgd.py::step`` row by row."""
    W = np.asarray(W, np.float32)
    lr = np.asarray(lr, np.float32)[:, None]
    alpha = np.asarray(alpha, np.float32)[:, None]
    l2, l1 = np.float32(l2), np.float32(l1)
    with jax.default_matmul_precision("highest"):
        G, rsum = _sums(jnp.asarray(W), Xb, yb, loss=loss,
                        design_dtype=design_dtype,
                        fit_intercept=fit_intercept, lower=lower)
    m = np.float32(Xb.shape[0])
    g_data = np.asarray(G, np.float32) / m
    if lower == "accumulate":
        g_data = ref._bf16(g_data)
    coef = W[:, :-1] - lr * (g_data + alpha * l2 * W[:, :-1])
    coef = np.sign(coef) * np.maximum(np.abs(coef) - lr * alpha * l1,
                                      np.float32(0.0))
    g_b = np.asarray(rsum, np.float32) / m if fit_intercept \
        else np.zeros(len(W), np.float32)
    W = np.c_[coef, W[:, -1] - lr[:, 0] * g_b].astype(np.float32)
    return ref._bf16(W) if lower == "update" else W


@functools.partial(jax.jit, static_argnames=("design_dtype",))
def _score(W, Xt, yt, tie, *, design_dtype=None):
    Xt = ref._round(Xt.astype(jnp.float32), design_dtype)
    eta = jnp.dot(Xt, ref._round(W[:, :-1], design_dtype).T) \
        + W[:, -1][None, :]
    hit = jnp.sum((eta > 0) == (yt[:, None] > 0.5), axis=0)
    rms = jnp.sqrt(jnp.mean(eta ** 2, axis=0))
    return hit, jnp.sum(jnp.abs(eta) < tie * rms[None, :], axis=0)


def score_many(W, Xt, yt, tie=0.0, design_dtype=None):
    """(accuracy of every row of ``W`` on the held-out rows, the count of
    held-out rows within ``tie`` x the decision's rms of its boundary)."""
    with jax.default_matmul_precision("highest"):
        hit, near = _score(jnp.asarray(W, jnp.float32), Xt, yt,
                           jnp.float32(tie), design_dtype=design_dtype)
    n = float(Xt.shape[0])
    return np.asarray(hit, np.float64) / n, np.asarray(near, np.int64)


# -- the search ------------------------------------------------------------------

class Problem:
    """The data of one search as the reference holds it: its own split of
    the device arrays ``X (n, d)`` / ``y (n,)`` (0/1), the training rows
    gathered block by block (float32), the held-out rows, the draw."""

    def __init__(self, X, y, n_rows, chips, *, parameters, max_iter, eta,
                 test_size, random_state, hyper, split_fn=split,
                 partition_fn=partition):
        self.max_iter, self.eta, self.hyper = int(max_iter), int(eta), hyper
        self.params, self.bracket_of = draw(parameters, max_iter, eta,
                                            random_state)
        self.train_idx, self.test_idx = split_fn(n_rows, chips, test_size,
                                                 random_state)
        self.B, self.S = partition_fn(len(self.train_idx), chips)
        take = jax.jit(lambda a, i: jnp.take(a, i, axis=0))
        cuts = [self.train_idx[b * self.S:(b + 1) * self.S]
                for b in range(self.B)]
        self.blocks = [(take(X, jnp.asarray(c)), take(y, jnp.asarray(c)))
                       for c in cuts if len(c)]
        self.B = len(self.blocks)
        te = jnp.asarray(self.test_idx)
        self.Xt, self.yt = take(X, te), take(y, te)
        self.d = int(X.shape[1])
        self.models = {}          # bracket -> its model ids
        for mid, s in enumerate(self.bracket_of):
            self.models.setdefault(s, []).append(mid)

    def advance(self, W, mids, start, stop, design_dtype=None, lower=None,
                block_of=None):
        """The models ``mids`` (rows of ``W``, all at ``start`` calls) make
        calls ``start .. stop - 1``."""
        h = self.hyper
        alpha = np.asarray([self.params[m]["alpha"] for m in mids])
        eta0 = np.asarray([self.params[m]["eta0"] for m in mids])
        for i in range(start, stop):
            Xb, yb = self.blocks[(block_of or (lambda c: c % self.B))(i)]
            lr = [ref.learning_rate(i + 1, h["schedule"], e, h["power_t"], a)
                  for e, a in zip(eta0, alpha)]
            W = step_many(W, Xb, yb, lr, alpha, h["l2"], h["l1"],
                          loss=h["loss"], design_dtype=design_dtype,
                          fit_intercept=h["fit_intercept"], lower=lower)
        return W

    def scores(self, W, tie=0.0, design_dtype=None, on=None):
        Xt, yt = on or (self.Xt, self.yt)
        return score_many(W, Xt, yt, tie, design_dtype)


def search(p, design_dtype=None, lower=None, fault=None):
    """The whole search on the problem ``p``: ``{"history": [{model_id,
    bracket, partial_fit_calls, score}] in the order scored, "calls":
    {model id: final calls}, "W": {model id: final weights}, "score":
    {model id: last score}, "cuts": [{bracket, calls, scores {id: score},
    kept [ids]}]}``.

    ``fault`` runs it wrongly in ONE named way, for showing that the check
    fails it: ``"bottom"`` (a cut keeps the worst), ``"train_rows"`` (scored
    on the first training block), ``"short"`` (every rung trains one call
    less than it records), ``"one_block"`` (every call trains block 0: the
    ``data_shards`` partition of one chip), ``"reversed"`` (call ``i`` trains
    block ``B - 1 - i mod B``)."""
    out = {"history": [], "calls": {}, "W": {}, "score": {}, "cuts": []}
    on = p.blocks[0] if fault == "train_rows" else None
    block_of = {"one_block": lambda c: 0,
                "reversed": lambda c: p.B - 1 - c % p.B}.get(fault)
    for s, n, r in brackets(p.max_iter, p.eta):
        alive = list(p.models[s])
        W = np.zeros((len(alive), p.d + 1), np.float32)
        at = 0
        for n_alive, calls in rungs(n, r, p.max_iter, p.eta):
            stop = calls - 1 if fault == "short" and calls > 1 else calls
            W = p.advance(W, alive, at, stop, design_dtype, lower, block_of)
            at = stop
            sc, _ = p.scores(W, 0.0, design_dtype, on)
            scores = {m: float(v) for m, v in zip(alive, sc)}
            for m in alive:
                out["history"].append({"model_id": m, "bracket": s,
                                       "partial_fit_calls": calls,
                                       "score": scores[m]})
                out["calls"][m], out["score"][m] = calls, scores[m]
            for j, m in enumerate(alive):
                out["W"][m] = W[j]
            if calls >= p.max_iter:
                break
            k = max(1, len(alive) // p.eta)
            kept = keep(scores, k) if fault != "bottom" else \
                sorted(scores, key=lambda m: (scores[m], m))[:k]
            out["cuts"].append({"bracket": s, "calls": calls,
                                "scores": scores, "kept": sorted(kept)})
            W = W[[alive.index(m) for m in sorted(kept)]]
            alive = sorted(kept)
    return out


def replay(p, calls, design_dtype=None, lower=None, known=None,
           block_of=None):
    """``{model id: weights}`` of every model of ``calls`` (``{model id:
    calls}``) trained from zero for that many calls on blocks ``i mod B``.
    ``known``: a finished :func:`search` at the same precision, whose
    weights are taken where it trained a model exactly that far.
    ``block_of``: another map from the call to its block, for a fault."""
    out, todo = {}, {}
    for m, c in calls.items():
        if known is not None and known["calls"].get(m) == c:
            out[m] = known["W"][m]
        else:
            todo.setdefault((p.bracket_of[m], c), []).append(m)
    for (_, c), mids in sorted(todo.items()):
        W = p.advance(np.zeros((len(mids), p.d + 1), np.float32), mids, 0,
                      c, design_dtype, lower, block_of)
        out.update(zip(mids, W))
    return out
