"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    chiprun -- python chip_smoke.py             # one chip
    chiprun --chips 4 -- python chip_smoke.py   # a four-chip host

One process drives ``LogisticRegression`` — the BASELINE north-star model,
at its full width of 256 features — through the entry points a user calls,
under the DEFAULT config (``dtype="auto"``, ``pallas_stream=True``,
``stream_mesh=0``), on every chip jax shows:

1. resident      4,194,304 x 256 seeded rows born sharded on the device(s),
                 ``fit`` (L-BFGS, 5 iterations), loss and gradient at the
                 fitted ``coef_`` against the plain-f32 reference
                 (``models/solvers/reference.py``) on a 65,536-row sample;
2. predict       ``predict_proba`` / ``score`` against the reference;
3. objective     one streamed ``value_and_grad`` over a seeded 1 GiB
                 ``np.memmap``: fused and XLA flavour, full mesh and one
                 device, against each other and against the reference, and
                 zero compiles after pass 1;
4. streamed      the memmap ``fit`` with the kernels on and off, what the
                 pass stats say engaged (stacked layout, K, dispatches,
                 native reader, fused), and a converged streamed fit against
                 a converged resident fit of the same rows;
5. kernels       every Pallas kernel a TPU auto-gate can select, compiled
                 (``interpret=False``) at production shape, f32 and the
                 bf16 ``mxu`` variant, against its XLA flavour;
6. pca           ``PCA(64, svd_solver="randomized")`` on 1,048,576 x 512
                 seeded rows born sharded (a planted 64-dimensional
                 subspace): ``fit`` through TSQR and ``transform``, against
                 the exact plain-f32 reference
                 (``models/solvers/reference_pca.py``: the covariance of all
                 the rows; the projection of a 65,536-row sample) under the
                 benchmark's bands (``benchmark/tolerances_pca.py``).

It exits non-zero — and prints no result line — unless jax's default backend
is a TPU and every step held; a ``RuntimeWarning`` from ``dask_ml_tpu`` is an
error. Its timings are SMOKE timings of single cold calls, compilation
included — not benchmark figures. The last line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The step functions take ``interpret=True`` for the CPU rehearsal in
``tests/test_chip_smoke.py`` (tiny rows, interpret-mode kernels, the TPU
gates' choices requested explicitly); ``__main__`` has no CPU mode.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import sys
import tempfile
import time
import traceback
import warnings

import numpy as np

import jax
import jax.numpy as jnp

import dask_ml_tpu  # noqa: F401  (places the compile cache before any compile)
from dask_ml_tpu import config
from dask_ml_tpu import observability as obs
from dask_ml_tpu.linear_model import LogisticRegression
from dask_ml_tpu.models.solvers import reference
from dask_ml_tpu.parallel import as_sharded


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Row counts are what a smoke can afford; the width is never cut."""

    d: int = 256
    resident_rows: int = 4_194_304     # 4 GiB of f32 over the visible chips
    sample_rows: int = 65_536
    stream_rows: int = 1_048_576       # a 1 GiB float32 memmap
    stream_block_rows: int = 0         # 0 = the default: auto (262,144 here)
    kernel_rows: int = 65_536          # one chip's slab of an auto block on 4
    n_classes: int = 8
    lloyd_k: int = 64
    lloyd_d: int = 128
    pca_rows: int = 1_048_576          # 2 GiB of f32 at the source's width
    pca_d: int = 512
    pca_k: int = 64


class SmokeFailure(AssertionError):
    """A check did not hold (raised, not ``assert``: -O must not skip it)."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def rel_max(a, b):
    """max|a - b| / max|b| — relative to the reference's largest entry, so
    entries that are zero by symmetry do not blow the ratio up."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-30))


# Tolerances, each with its reason.
#
# A bf16 design matrix rounds every x_ij (and the beta it multiplies) to 8
# mantissa bits, relative error <= 2^-8 per factor, with f32 accumulation.
# The x roundings are independent and average out over the rows of a sum;
# the beta rounding is the same for every row and does not: it moves the
# gradient by H @ d_beta whatever n is. So a gradient is judged against the
# problem's gradient SCALE — the largest entry of the reference gradient at
# beta = 0 on the same rows — never against the gradient at the point
# itself, which at a fitted coef_ is nearly zero while the rounding error
# is not. (Rehearsed on the CPU at d = 256, 65,536 rows, bf16 design: loss
# off by 2e-5 relative, gradient by 1.4e-4 absolute = 3e-3 of the scale.)
TOL_BF16_LOSS = 1e-3
TOL_BF16_GRAD = 1e-2
# Same arithmetic, different summation order (shards, tiles): f32 roundoff.
TOL_F32_ORDER = 1e-5
# An f32 matmul at the TPU's DEFAULT precision multiplies in bf16 (one
# pass), so the "f32" XLA flavours on the chip carry bf16-sized error too.
TOL_TPU_DEFAULT_PRECISION = TOL_BF16_GRAD
# Kernel against its XLA flavour on IDENTICAL (pre-rounded) operands. On
# the v5e the MXU multiplies f32 operands in bf16 at the DEFAULT matmul
# precision — Mosaic's and XLA's alike — so an "f32" kernel matches its XLA
# flavour at default precision (<= 2.6e-4 of the largest entry, PR 21) while
# both sit up to 2.4e-3 (GLM/SGD) and 1.1e-2 (the Lloyd inertia, where the
# rounded x.c cross term meets the exact ||c||^2 in a cancellation) off the
# f32 answer. The kernel's own in-VMEM bf16 cast of the residual costs
# another 2.1e-3. A masking, tiling or accumulation bug is O(1).
TOL_KERNEL = 1e-2
# Converged streamed fit against converged resident fit: both stop at
# gradient norm <= 1e-3 (CONVERGED below) on an objective whose Hessian is
# ~0.1 I, i.e. within ~1e-2 of the same optimum; rehearsed on the CPU at
# d = 256 the two coef_ differ by 5e-3 of the largest entry.
TOL_CONVERGED_COEF = 5e-2
# Not tighter than 1e-3: a bf16 design rounds beta before it multiplies, so
# the objective is a staircase in beta with steps of 2^-8 |beta_j|. Asked
# for 1e-4, the host Armijo search backtracks up to 30 passes an iteration
# on the plateaus (2,147 data passes in 100 iterations in the rehearsal).
CONVERGED = dict(max_iter=50, tol=1e-3)
# Three L-BFGS iterations with the kernels on against off: same host
# algorithm, gradients that differ by TOL_BF16_GRAD.
TOL_FUSED_VS_XLA_COEF = 5e-2


def grad_err(g, g_ref, scale):
    """max|g - g_ref| as a fraction of the gradient scale (see above)."""
    g = np.asarray(g, np.float64)
    return float(np.max(np.abs(g - np.asarray(g_ref, np.float64))) / scale)


def device_info():
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _fit_kwargs(interpret):
    """On the chip: nothing — the defaults must choose bf16 and the fused
    kernel by themselves. In the CPU rehearsal the same choices are
    REQUESTED, with the kernels in interpret mode."""
    if not interpret:
        return {}
    return {"fit_dtype": "bfloat16",
            "solver_kwargs": {"use_pallas": True, "pallas_interpret": True}}


def _stream_config(sizes, interpret, **kw):
    if interpret:
        kw.setdefault("pallas_stream_interpret", True)
    return config.set(stream_block_rows=sizes.stream_block_rows, **kw)


# -- data ---------------------------------------------------------------------

def make_resident(sizes, seed=0):
    """(X, y) ShardedArrays of ``resident_rows`` x ``d`` seeded rows, BORN
    row-sharded over the default mesh (each chip draws its own rows —
    nothing is staged through the host or piled on device 0)."""
    from dask_ml_tpu.parallel import default_mesh
    from dask_ml_tpu.parallel.mesh import row_sharding

    mesh = default_mesh()
    n, d = sizes.resident_rows, sizes.d

    def gen(key):
        kb, kx, ky = jax.random.split(key, 3)
        beta = jax.random.normal(kb, (d,)) / np.sqrt(d)
        X = jax.random.normal(kx, (n, d), jnp.float32)
        p = jax.nn.sigmoid(2.0 * (X @ beta))
        y = (jax.random.uniform(ky, (n,)) < p).astype(jnp.float32)
        return X, y

    X, y = jax.jit(
        gen, out_shardings=(row_sharding(mesh, 2), row_sharding(mesh, 1))
    )(jax.random.PRNGKey(seed))
    return as_sharded(X, mesh=mesh), as_sharded(y, mesh=mesh)


def make_memmap(sizes, workdir, seed=1):
    """(X memmap opened read-only, y ndarray): ``stream_rows`` x ``d``
    seeded float32 rows written chunk by chunk."""
    n, d = sizes.stream_rows, sizes.d
    rng = np.random.default_rng(seed)
    w = rng.standard_normal(d) / np.sqrt(d)
    path = os.path.join(workdir, "chip_smoke_X.f32")
    mm = np.memmap(path, dtype=np.float32, mode="w+", shape=(n, d))
    y = np.empty(n, np.float32)
    step = 131_072
    for lo in range(0, n, step):
        hi = min(lo + step, n)
        blk = rng.standard_normal((hi - lo, d), dtype=np.float32)
        mm[lo:hi] = blk
        noise = rng.standard_normal(hi - lo)
        y[lo:hi] = (2.0 * (blk @ w) + noise > 0).astype(np.float32)
    mm.flush()
    del mm
    return np.memmap(path, dtype=np.float32, mode="r", shape=(n, d)), y


# -- step 1: resident fit -----------------------------------------------------

@functools.partial(jax.jit, static_argnames=("use_pallas", "mesh",
                                             "interpret"))
def _system_value_and_grad(beta, data, y, mask, n_rows, lam, pmask,
                           use_pallas, mesh, interpret):
    """Loss and gradient through the SAME loss the resident solvers
    minimise (``solvers._select_loss``: the fused kernel under shard_map +
    custom_vjp, or the XLA objective), the intercept as the last entry of
    beta and ``data`` as wide as the features, as an lbfgs fit runs it."""
    from dask_ml_tpu.models.solvers import solvers as S

    loss = S._select_loss(use_pallas, data, y, mask, n_rows, lam, pmask,
                          0.5, "logistic", "l2", mesh, interpret,
                          intercept=True)
    return jax.value_and_grad(loss)(beta)


def step_resident(sizes, interpret=False, state=None, facts=None):
    from dask_ml_tpu.models.glm import _prepare_fit
    from dask_ml_tpu.models.solvers import solvers as S

    state = {} if state is None else state
    facts = {} if facts is None else facts
    X, y = make_resident(sizes)
    n_dev = len(jax.devices())
    check(len(X.data.sharding.device_set) == n_dev,
          f"X lives on {len(X.data.sharding.device_set)} of {n_dev} devices")

    clf = LogisticRegression(solver="lbfgs", max_iter=5, tol=0.0,
                             **_fit_kwargs(interpret))
    clf.fit(X, y)
    facts["fit_dtype"] = clf.fit_dtype_
    check(clf.fit_dtype_ == "bfloat16",
          f"dtype='auto' resolved to {clf.fit_dtype_}, not bfloat16")
    facts["fused"] = clf.solver_info_.get("fused")
    check(facts["fused"] is True,
          f"the fused GLM kernel was not selected: {clf.solver_info_}")
    facts["intercept"] = clf.solver_info_.get("intercept")
    check(facts["intercept"] == "scalar",
          f"the intercept rode as a column of X: {clf.solver_info_}")
    check(clf.n_iter_ == 5, f"n_iter_ {clf.n_iter_} != 5")

    # nothing piled on one device: bytes in use, X still alive
    stats = [d.memory_stats() for d in jax.devices()]
    if all(s is not None for s in stats):
        used = [int(s["bytes_in_use"]) for s in stats]
        facts["bytes_in_use"] = used
        check(max(used) <= 1.05 * min(used) + (64 << 20),
              f"device memory is unbalanced: {used}")
    else:
        facts["bytes_in_use"] = "not reported by this backend"

    # the seeded sample the reference runs on
    m = sizes.sample_rows
    Xh = np.asarray(X.data[:m])
    yh = np.asarray(y.data[:m])
    coef = np.asarray(clf.coef_, np.float32).ravel()
    b0 = np.float32(np.ravel(clf.intercept_)[0])
    pmask, lam = clf._penalty_setup(sizes.d + 1, X.n_rows)

    def ref_at(c, b):
        v, gc, gb = reference.logreg_value_and_grad(c, b, Xh, yh, lam)
        return float(v), np.r_[np.asarray(gc), float(gb)]

    ref0, g0 = ref_at(np.zeros_like(coef), 0.0)
    ref_v, _ = ref_at(coef, b0)
    scale = float(np.max(np.abs(g0)))
    facts["loss_at_zero"], facts["loss_at_fit"] = ref0, ref_v
    check(ref_v < ref0, f"the loss did not fall: {ref0} -> {ref_v}")

    # ... and the system's own loss path on the same sample
    Xs, ys = as_sharded(Xh, mesh=X.mesh), as_sharded(yh, mesh=X.mesh)
    mask = Xs.row_mask(dtype=jnp.float32)
    data, y_enc, _ = _prepare_fit(
        Xs.data, ys.data, mask, fit_intercept=False,
        to_bf16=clf.fit_dtype_ == "bfloat16", encode=True,
    )
    check(data.shape[1] == sizes.d, f"prep widened X to {data.shape}")
    use_pallas = S._resolve_pallas(True if interpret else None, X.mesh,
                                   "logistic", data)
    check(use_pallas, "the fused GLM kernel's gate refuses the sample shape")
    # at the fitted coef_, and halfway to it — where the gradient is still
    # large, so a gradient that is a MULTIPLE of the right one cannot hide
    # behind a near-zero value
    for name, t in (("fit", 1.0), ("half", 0.5)):
        rv, rg = ref_at(t * coef, t * b0)
        sys_v, sys_g = _system_value_and_grad(
            jnp.asarray(np.r_[t * coef, t * b0]), data, y_enc, mask, m,
            jnp.float32(lam), jnp.asarray(pmask), use_pallas=True,
            mesh=X.mesh, interpret=interpret,
        )
        lerr = abs(float(sys_v) - rv) / abs(rv)
        gerr = grad_err(sys_g, rg, scale)
        facts[f"loss_rel_err@{name}"] = lerr
        facts[f"grad_err_of_scale@{name}"] = gerr
        check(np.isfinite(np.asarray(sys_g)).all(), "non-finite gradient")
        check(lerr <= TOL_BF16_LOSS,
              f"loss at {name}: {float(sys_v)} vs reference {rv}")
        check(gerr <= TOL_BF16_GRAD,
              f"gradient at {name} off by {gerr} of the gradient scale")

    state.update(clf=clf, sample=(Xh, yh), mesh=X.mesh)
    return facts


# -- step 2: predict ----------------------------------------------------------

def step_predict(sizes, interpret=False, state=None, facts=None):
    clf = state["clf"]
    Xh, yh = state["sample"]
    coef = np.asarray(clf.coef_, np.float32).ravel()
    b0 = np.float32(np.ravel(clf.intercept_)[0])
    ref_p = np.asarray(reference.logreg_proba(coef, b0, Xh))
    Xs = as_sharded(Xh, mesh=state["mesh"])
    proba = clf.predict_proba(Xs)
    check(proba.shape == (len(Xh), 2), f"predict_proba shape {proba.shape}")
    check(np.isfinite(proba).all(), "non-finite probabilities")
    facts = {} if facts is None else facts
    facts["proba_max_err"] = float(np.max(np.abs(proba[:, 1] - ref_p)))
    # eta is an f32 matvec at the backend's default precision (bf16
    # multiplies on a TPU): |d eta| ~ 2^-9 |eta| moves a probability by
    # at most a quarter of that
    check(facts["proba_max_err"] <= TOL_TPU_DEFAULT_PRECISION,
          f"predict_proba off by {facts['proba_max_err']}")
    ref_score = float(np.mean((ref_p > 0.5) == (yh > 0.5)))
    facts["score"], facts["ref_score"] = float(clf.score(Xs, yh)), ref_score
    # only rows whose probability sits within the band above of 0.5 can flip
    check(abs(facts["score"] - ref_score) <= 5e-3,
          f"score {facts['score']} vs reference {ref_score}")
    check(facts["score"] > 0.6, f"score {facts['score']} is chance level")
    return facts


# -- step 3: one streamed value_and_grad, every flavour ------------------------

def _streamed_vg(X, y, betas, interpret, sizes, **cfg):
    """[(value, grad)] of the streamed objective at each beta — one pass
    each, over one BlockStream, built exactly as ``_fit_streamed`` builds
    it — plus the compiles paid AFTER the first pass."""
    from dask_ml_tpu.models.solvers.streamed import StreamedObjective
    from dask_ml_tpu.parallel.streaming import BlockStream, stream_plan

    n, d = X.shape
    pmask, lam = LogisticRegression()._penalty_setup(d + 1, n)
    with _stream_config(sizes, interpret, **cfg):
        stream = BlockStream((X, y), block_rows=stream_plan(X))
        obj = StreamedObjective(
            stream, n, jnp.float32(lam), jnp.asarray(pmask), 0.5,
            "logistic", "l2", True,
            fit_dtype="bfloat16" if interpret else None,
        )
        out = [obj.value_and_grad(betas[0])]
        obs.counters_reset()
        out += [obj.value_and_grad(b) for b in betas[1:]]
        later_compiles = int(obs.counters_snapshot().get("recompiles", 0))
        flavor = obj._sb_flavor("vg")
    return out, later_compiles, flavor, dict(stream.stats)


def step_objective(sizes, interpret=False, state=None, facts=None):
    X, y = state["memmap"]
    n, d = X.shape
    n_dev = len(jax.devices())
    rng = np.random.default_rng(7)
    betas = [rng.standard_normal(d + 1) / np.sqrt(d) for _ in range(2)]
    # the reference over ALL streamed rows, resident in f32
    Xd, yd = jnp.asarray(np.asarray(X)), jnp.asarray(y)
    refs = []
    for b in [np.zeros(d + 1)] + betas:
        v, gc, gb = reference.logreg_value_and_grad(
            b[:-1], b[-1], Xd, yd, 1.0 / n
        )
        refs.append((float(v), np.r_[np.asarray(gc), float(gb)]))
    del Xd, yd
    scale = float(np.max(np.abs(refs.pop(0)[1])))

    facts, got = ({} if facts is None else facts), {}
    meshes = [("full", 0)] + ([("one", 1)] if n_dev > 1 else [])
    for fused in (True, False):
        for mesh_name, sm in meshes:
            key = f"{'fused' if fused else 'xla'}/{mesh_name}"
            out, later, (mxu, is_fused, _, reason), st = _streamed_vg(
                X, y, betas, interpret, sizes, pallas_stream=fused,
                stream_mesh=sm,
            )
            check(is_fused is fused,
                  f"{key}: fused={is_fused}, reason={reason!r}")
            check(st["sb_shards"] == (n_dev if sm == 0 else 1),
                  f"{key}: ran over {st['sb_shards']} shards")
            check(later == 0, f"{key}: {later} compiles after pass 1")
            got[key] = out
            for (v, g), (rv, rg) in zip(out, refs):
                tol_g = TOL_BF16_GRAD if fused \
                    else TOL_TPU_DEFAULT_PRECISION
                check(abs(v - rv) / abs(rv) <= TOL_BF16_LOSS,
                      f"{key}: value {v} vs reference {rv}")
                check(grad_err(g, rg, scale) <= tol_g,
                      f"{key}: gradient off by {grad_err(g, rg, scale)} "
                      f"of the gradient scale")
            facts[key] = {
                "mxu": None if mxu is None else jnp.dtype(mxu).name,
                "grad_err_of_scale": max(
                    grad_err(g, rg, scale)
                    for (_, g), (_, rg) in zip(out, refs)
                ),
            }
    # the D-times-wrong-gradient check: same beta, full mesh against one
    # device, for BOTH flavours (a double reduction leaves the value right
    # and multiplies the gradient by the shard count)
    if n_dev > 1:
        for flav in ("fused", "xla"):
            for (v, g), (v1, g1) in zip(got[f"{flav}/full"],
                                        got[f"{flav}/one"]):
                dv, dg = abs(v - v1) / abs(v1), rel_max(g, g1)
                facts[f"{flav}/full-vs-one"] = {"value": dv, "grad": dg}
                check(dv <= TOL_F32_ORDER and dg <= TOL_F32_ORDER,
                      f"{flav}: {n_dev} shards vs 1 differ by value "
                      f"{dv}, gradient {dg}")
    return facts


# -- step 4: streamed fits ----------------------------------------------------

def step_streamed(sizes, interpret=False, state=None, facts=None):
    X, y = state["memmap"]
    n_dev = len(jax.devices())
    facts = {} if facts is None else facts

    def fit(fused, **kw):
        # (the streamed solvers ignore the resident kernel's solver_kwargs)
        with _stream_config(sizes, interpret, pallas_stream=fused):
            return LogisticRegression(
                solver="lbfgs", **_fit_kwargs(interpret), **kw
            ).fit(X, y)

    on = fit(True, max_iter=3)
    st, info = dict(on._last_stream_stats), dict(on.solver_info_)
    # the LAST pass's staging clocks ride along as smoke timings: they say
    # which side of the double buffer a pass waits on
    facts["stats"] = {k: st.get(k) for k in (
        "superblock_k", "n_blocks", "block_rows",
        "dispatches_per_pass", "sb_shards", "native_reader",
        "native_reader_reason", "host_s", "put_s", "wait_s", "consume_s",
        "pass_s")}
    facts["fused_stream"] = info.get("fused_stream")
    facts["fit_dtype"] = info.get("fit_dtype")
    k = int(st.get("superblock_k", 0))
    check(k > 1, f"super-blocks did not engage: {st}")
    check(info.get("fused_stream") is True
          and info.get("fused_stream_reason") is None,
          f"fused_stream={info.get('fused_stream')}, "
          f"reason={info.get('fused_stream_reason')!r}")
    check(st["dispatches_per_pass"] == math.ceil(st["n_blocks"] / k),
          f"dispatches_per_pass {st['dispatches_per_pass']} != "
          f"ceil({st['n_blocks']}/{k})")
    check(st["sb_shards"] == n_dev and info["stream_shards"] == n_dev,
          f"streamed over {st['sb_shards']} of {n_dev} devices")
    check(st.get("native_reader") is True,
          f"the native block reader did not serve the memmap: "
          f"{st.get('native_reader_reason')!r}")
    check(info.get("fit_dtype") == "bfloat16",
          f"streamed fit_dtype {info.get('fit_dtype')}")

    # the same fit with the kernels off must match — on more than one chip
    # this is the check that catches a D-times-too-large gradient
    off = fit(False, max_iter=3)
    check(off.solver_info_["fused_stream"] is False
          and off.solver_info_["fused_stream_reason"] == "pallas-stream-off",
          f"kernels-off fit: {off.solver_info_}")
    facts["fused_vs_xla_coef"] = rel_max(on.coef_, off.coef_)
    check(facts["fused_vs_xla_coef"] <= TOL_FUSED_VS_XLA_COEF,
          f"kernels on vs off: coef differs by {facts['fused_vs_xla_coef']}")

    # the streamed and the resident path run DIFFERENT line searches
    # (Armijo on the host, optax zoom on the device), so iteration-limited
    # fits differ by construction: the band is stated for converged fits
    streamed = fit(True, **CONVERGED)
    resident = LogisticRegression(
        solver="lbfgs", **_fit_kwargs(interpret), **CONVERGED
    ).fit(as_sharded(np.asarray(X)), as_sharded(y))
    facts["n_iter"] = {"streamed": streamed.n_iter_,
                       "resident": resident.n_iter_,
                       "data_passes": streamed.solver_info_["data_passes"]}
    check(max(streamed.n_iter_, resident.n_iter_) < CONVERGED["max_iter"],
          f"not converged: {facts['n_iter']}")
    facts["streamed_vs_resident_coef"] = rel_max(
        np.r_[streamed.coef_.ravel(), streamed.intercept_],
        np.r_[resident.coef_.ravel(), resident.intercept_],
    )
    check(facts["streamed_vs_resident_coef"] <= TOL_CONVERGED_COEF,
          f"streamed vs resident coef differ by "
          f"{facts['streamed_vs_resident_coef']}")
    return facts


# -- step 5: every auto-selectable kernel, compiled, against its XLA flavour ---

def _sgd_xla_sums(x, nv, y, W, loss, codes):
    """The streamed SGD step's XLA flavour (models/sgd.py's ``local_sums``
    under autodiff): raw (loss sums, gradient sums) for stacked weights."""
    from dask_ml_tpu.ops.pallas_fused import sgd_objective_terms

    mask = (jnp.arange(x.shape[0]) < nv).astype(jnp.float32)

    def one(w, yy):
        def f(w):
            eta = x @ w[:-1] + w[-1]
            return jnp.sum(sgd_objective_terms(eta, yy, loss)[0] * mask)

        return jax.value_and_grad(f)(w)

    if W.ndim == 1:
        return one(W, y)
    N = W.shape[0]
    Y = (y[None, :] == jnp.arange(N, dtype=y.dtype)[:, None]
         ).astype(jnp.float32) if codes else jnp.broadcast_to(y, (N,) + y.shape)
    return jax.vmap(one)(W, Y)


def kernel_inputs(sizes):
    """Every operand the kernel cases read, drawn in ONE program."""
    S, d, C = sizes.kernel_rows, sizes.d, sizes.n_classes
    k, kd = sizes.lloyd_k, sizes.lloyd_d
    f32 = jnp.float32

    @jax.jit
    def gen(key):
        ks = jax.random.split(key, 8)
        nv = jnp.int32(S - 37)         # a ragged valid-row prefix
        mask = (jnp.arange(S) < nv).astype(f32)
        X = jax.random.normal(ks[0], (S, d), f32)
        # well-separated blobs for the Lloyd kernels: no assignment sits
        # on a tie that a different matmul rounding could flip
        centers = 3.0 * jax.random.normal(ks[5], (k, kd), f32)
        lab = jax.random.randint(ks[6], (S,), 0, k)
        return dict(
            X=X, nv=nv, mask=mask,
            # resident GLM kernels see d + 1 columns (the intercept
            # column is data there)
            X1=jnp.concatenate([X, mask[:, None]], axis=1),
            beta=jax.random.normal(ks[1], (d + 1,), f32) / np.sqrt(d),
            B=jax.random.normal(ks[2], (C, d + 1), f32) / np.sqrt(d),
            y=(jax.random.uniform(ks[3], (S,)) < 0.5).astype(f32),
            codes=jax.random.randint(ks[4], (S,), 0, C).astype(f32),
            centers=centers,
            Xb=centers[lab] + 0.3 * jax.random.normal(ks[7], (S, kd), f32),
        )

    return gen(jax.random.PRNGKey(11))


def _vg_intercept(out):
    """(value, (d,) gradient, intercept gradient) -> (value, (d + 1,))."""
    v, g, gb = out
    return v, jnp.concatenate([g, gb[None]])


def kernel_cases(sizes):
    """[(name, kernel(inp, interpret) -> outputs, xla(inp) -> outputs)] over
    the dict from :func:`kernel_inputs`. Each pair runs on IDENTICAL
    operands: the bf16 variants see operands already rounded to bf16
    (upcast to f32 for the XLA side), so the comparison is about the
    kernel, not about the rounding policy."""
    from dask_ml_tpu.models import kmeans as KM
    from dask_ml_tpu.models.solvers import streamed as ST
    from dask_ml_tpu.ops import pallas_fused as pf

    C = sizes.n_classes
    f32, bf16 = jnp.float32, jnp.bfloat16
    L = "logistic"

    def r(a, on):                      # the bf16 policy's operand rounding
        return a.astype(bf16).astype(f32) if on else a

    cases = []

    def add(name, kernel, xla):
        cases.append((name, kernel, xla))

    for dt in (f32, bf16):
        n, b = jnp.dtype(dt).name, dt == bf16
        add(f"fused_glm_value_grad[{n}]",
            lambda a, i, dt=dt: pf.fused_glm_value_grad(
                a["X1"].astype(dt), a["nv"], a["y"], a["beta"], L,
                interpret=i),
            lambda a, b=b: ST._block_val_grad(
                r(a["beta"], b), r(a["X1"], b), a["y"], a["mask"], L, False))
        # ... and as every lbfgs / gradient_descent / proximal_grad fit
        # calls it: d columns, the intercept an f32 scalar operand that
        # no policy rounds
        add(f"fused_glm_value_grad[{n},intercept]",
            lambda a, i, dt=dt: _vg_intercept(pf.fused_glm_value_grad(
                a["X"].astype(dt), a["nv"], a["y"], a["beta"][:-1], L,
                interpret=i, intercept=a["beta"][-1])),
            lambda a, b=b: ST._block_val_grad(
                jnp.concatenate([r(a["beta"][:-1], b), a["beta"][-1:]]),
                r(a["X"], b), a["y"], a["mask"], L, True))
        add(f"fused_glm_multi_value_grad[{n}]",
            lambda a, i, dt=dt: pf.fused_glm_multi_value_grad(
                a["X1"].astype(dt), a["nv"], a["codes"], a["B"], L,
                interpret=i),
            lambda a, b=b: ST._block_val_grad_multi(
                r(a["B"], b), r(a["X1"], b), a["codes"], a["mask"], L,
                False, C))
    add("fused_glm_value_grad_hess[float32]",
        lambda a, i: pf.fused_glm_value_grad_hess(
            a["X1"], a["nv"], a["y"], a["beta"], L, interpret=i),
        lambda a: ST._block_val_grad_hess(
            a["beta"], a["X1"], a["y"], a["mask"], L, False))

    # streamed kernels: f32 blocks, bf16 cast in VMEM when mxu is set
    for mxu in (None, bf16):
        n, b = ("float32", False) if mxu is None else ("mxu-bfloat16", True)
        for kind, fn in (("val", ST._block_val), ("vg", ST._block_val_grad),
                         ("vgh", ST._block_val_grad_hess)):
            if kind != "vg" and mxu is not None:
                continue               # val/vgh stay f32 when streamed
            add(f"fused_glm_stream[{kind},{n}]",
                lambda a, i, kind=kind, mxu=mxu: pf.fused_glm_stream(
                    kind, a["X"], a["nv"], a["y"], a["beta"], L, True,
                    mxu=mxu, interpret=i),
                lambda a, fn=fn, b=b: fn(
                    r(a["beta"], b), r(a["X"], b), a["y"], a["mask"], L,
                    True))
        for kind, fn in (("val", ST._block_val_multi),
                         ("vg", ST._block_val_grad_multi)):
            add(f"fused_glm_multi_stream[{kind},{n}]",
                lambda a, i, kind=kind, mxu=mxu: pf.fused_glm_multi_stream(
                    kind, a["X"], a["nv"], a["codes"], a["B"], L, True,
                    mxu=mxu, interpret=i),
                lambda a, fn=fn, b=b: fn(
                    r(a["B"], b), r(a["X"], b), a["codes"], a["mask"], L,
                    True, C))
        add(f"fused_sgd_block_grad[{n}]",
            lambda a, i, mxu=mxu: pf.fused_sgd_block_grad(
                a["X"], a["nv"], a["y"], a["beta"], 1.0, "log_loss",
                mxu=mxu, interpret=i),
            lambda a, b=b: _sgd_xla_sums(
                r(a["X"], b), a["nv"], a["y"], r(a["beta"], b), "log_loss",
                False))
        # a search cohort's narrowest slot rung: ONE stacked weight row
        add(f"fused_sgd_many_block_grad[N=1,{n}]",
            lambda a, i, mxu=mxu: pf.fused_sgd_many_block_grad(
                a["X"], a["nv"], a["y"], a["B"][:1], jnp.ones((1,), f32),
                "log_loss", False, mxu=mxu, interpret=i),
            lambda a, b=b: _sgd_xla_sums(
                r(a["X"], b), a["nv"], a["y"], r(a["B"][:1], b), "log_loss",
                False))
        for cd in (True, False):
            add(f"fused_sgd_many_block_grad[codes={cd},{n}]",
                lambda a, i, mxu=mxu, cd=cd: pf.fused_sgd_many_block_grad(
                    a["X"], a["nv"], a["codes"] if cd else a["y"], a["B"],
                    jnp.ones((C,), f32), "log_loss", cd, mxu=mxu,
                    interpret=i),
                lambda a, b=b, cd=cd: _sgd_xla_sums(
                    r(a["X"], b), a["nv"], a["codes"] if cd else a["y"],
                    r(a["B"], b), "log_loss", cd))

    def xla_stats(a, mxu=None):
        return KM._block_assign_stats(a["Xb"], a["mask"], a["centers"],
                                      mxu_dtype=mxu)

    add("fused_lloyd_stats",
        lambda a, i: pf.fused_lloyd_stats(a["Xb"], a["nv"], a["centers"],
                                          interpret=i),
        xla_stats)
    add("fused_assign_update",
        lambda a, i: pf.fused_assign_update(a["Xb"], a["mask"], a["centers"],
                                            interpret=i)[2:],
        xla_stats)
    for mxu in (None, bf16):
        n = "float32" if mxu is None else "mxu-bfloat16"
        add(f"fused_kmeans_block_stats[{n}]",
            lambda a, i, mxu=mxu: pf.fused_kmeans_block_stats(
                a["Xb"], a["nv"], a["centers"], mxu=mxu, interpret=i),
            functools.partial(xla_stats, mxu=mxu))
    return cases


def step_kernels(sizes, interpret=False, state=None, facts=None):
    """One program per case: the kernel, and its XLA flavour twice — at
    the backend's DEFAULT matmul precision (what the XLA flavour runs at)
    and at "highest" (the f32 answer). The kernel must sit within
    TOL_KERNEL of one of them: as accurate as the flavour it replaces, or
    more. Both distances are recorded."""
    facts = {} if facts is None else facts
    inp = kernel_inputs(sizes)

    def tup(out):
        return tuple(out) if isinstance(out, (tuple, list)) else (out,)

    bad = []
    for name, kernel, xla in kernel_cases(sizes):
        def run(a, kernel=kernel, xla=xla):
            got = tup(kernel(a, interpret))
            at_default = tup(xla(a))
            with jax.default_matmul_precision("highest"):
                at_highest = tup(xla(a))
            return got, at_default, at_highest

        t0 = time.perf_counter()
        got, at_default, at_highest = jax.block_until_ready(
            jax.jit(run)(inp)
        )
        errs = {}
        for label, want in (("default", at_default),
                            ("highest", at_highest)):
            check(len(got) == len(want),
                  f"{name}: {len(got)} outputs vs {len(want)}")
            for g, w in zip(got, want):
                check(g.shape == w.shape,
                      f"{name}: shape {g.shape} vs {w.shape}")
                check(bool(jnp.isfinite(g).all()),
                      f"{name}: non-finite output")
            errs[label] = max(rel_max(g, w) for g, w in zip(got, want))
        facts[name] = {"rel_err_vs_xla_default": errs["default"],
                       "rel_err_vs_xla_highest": errs["highest"],
                       "smoke_s": round(time.perf_counter() - t0, 2)}
        if min(errs.values()) > TOL_KERNEL:   # run every case, then fail
            bad.append(f"{name}: off by {errs['default']:.2e} (default "
                       f"precision) / {errs['highest']:.2e} (highest) of "
                       f"the largest entry, tol {TOL_KERNEL}")
    check(not bad, "; ".join(bad))
    return facts


# -- step 6: the third family, PCA by randomized SVD through TSQR --------------

def make_planted(sizes, seed=2):
    """``pca_rows`` x ``pca_d`` seeded rows BORN row-sharded, from the
    benchmark configuration ``pca_1b_x512``'s own distribution
    (``benchmark/families/pca.py``): ``pca_k`` planted orthonormal
    directions with covariance eigenvalues falling geometrically from 64 to
    16 over unit isotropic noise, plus a mean of order one."""
    from benchmark.families import pca as family
    from dask_ml_tpu.parallel import default_mesh
    from dask_ml_tpu.parallel.mesh import row_sharding

    mesh = default_mesh()
    n, d = sizes.pca_rows, sizes.pca_d
    spec = {"components": sizes.pca_k, "eigen_top": 64.0,
            "eigen_bottom": 16.0, "mean_scale": 1.0}
    hp = family.planted_params(np.random.default_rng(seed), d, spec)
    X = jax.jit(lambda key: family.planted_rows(key, n, d, hp, spec)[0],
                out_shardings=row_sharding(mesh, 2))(jax.random.PRNGKey(seed))
    return as_sharded(X, mesh=mesh)


def step_pca(sizes, interpret=False, state=None, facts=None):
    from benchmark import tolerances_pca as T
    from benchmark.families._common import device_rows
    from dask_ml_tpu.decomposition import PCA
    from dask_ml_tpu.models.solvers import reference_pca

    facts = {} if facts is None else facts
    k = sizes.pca_k
    X = make_planted(sizes)
    n = X.n_rows
    facts["shards"] = len(X.data.sharding.device_set)
    check(facts["shards"] == len(jax.devices()),
          f"X lives on {facts['shards']} of {len(jax.devices())} devices")
    # no padding rows: the reference below reads the shards as they are
    check(X.data.shape[0] == n, f"{n} rows padded to {X.data.shape[0]}")
    est = PCA(n_components=k, svd_solver="randomized", random_state=0).fit(X)
    scores = est.transform(X)
    info = dict(est.solver_info_)
    facts.update(solver_info=info, fit_dtype=est.fit_dtype_)
    check(info["solver"] == "randomized" and est.fit_dtype_ == "float32",
          f"the randomized float32 solver did not carry the fit: {info}, "
          f"{est.fit_dtype_}")

    exact = reference_pca.pca_exact(reference_pca.shard_blocks(X.data), k)
    check(exact["n"] == n, f"the reference saw {exact['n']} of {n} rows")
    bad = []
    for name, (value, band) in T.readings(
            exact, est.mean_, est.components_, est.explained_variance_,
            est.explained_variance_ratio_, info["size"],
            info["n_iter"]).items():
        facts[name], facts[name + "_band"] = value, band
        if not value <= band:
            bad.append(f"{name} {value:.3e} > {band:.3e}")

    m = min(sizes.sample_rows, n // facts["shards"])   # of the first shard
    want = reference_pca.transform(device_rows(X, m), est.mean_,
                                   est.components_)
    got = device_rows(scores, m)
    facts["transform"] = T.transform_reading(np.asarray(got), want)
    if not facts["transform"] <= T.TOL_TRANSFORM:
        bad.append(f"transform off by {facts['transform']:.3e} of the score "
                   f"scale (band {T.TOL_TRANSFORM:.0e})")
    check(not bad, "; ".join(bad))
    return facts


STEPS = (
    ("resident", step_resident),
    ("predict", step_predict),
    ("objective", step_objective),
    ("streamed", step_streamed),
    ("kernels", step_kernels),
    ("pca", step_pca),
)


# -- driver -------------------------------------------------------------------

class _CacheEvents:
    """Persistent-compile-cache hits and misses, from jax.monitoring."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _peak_memory():
    stats = [d.memory_stats() for d in jax.devices()]
    if any(s is None for s in stats):
        return "not reported by this backend"
    return [int(s.get("peak_bytes_in_use", 0)) for s in stats]


def main():
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; jax's default backend here is "
                 f"{backend!r} ({jax.devices()[0].device_kind})")
    warnings.filterwarnings("error", category=RuntimeWarning,
                            module=r"dask_ml_tpu")
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # the version string is only a label
        libtpu = "unknown"
    dev = device_info()
    print(f"chip_smoke: {dev['count']} x {dev['kind']} ({dev['platform']}); "
          f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
          f"libtpu {libtpu}; compile cache at "
          f"{jax.config.jax_compilation_cache_dir}", flush=True)

    sizes = Sizes()
    cache = _CacheEvents()
    obs.install_recompile_tracking()
    rows, state, ok = [], {}, True
    with tempfile.TemporaryDirectory() as workdir:
        t0 = time.perf_counter()
        state["memmap"] = make_memmap(sizes, workdir)
        print(f"  setup: {sizes.stream_rows} x {sizes.d} memmap written in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
        for name, fn in STEPS:
            obs.counters_reset()
            h0, m0 = cache.hits, cache.misses
            t0 = time.perf_counter()
            facts, err = {}, None   # filled in place: a failed step keeps
            try:                    # what it had established
                fn(sizes, interpret=False, state=state, facts=facts)
            except Exception as exc:  # report every step, then fail
                traceback.print_exc()
                err = f"{type(exc).__name__}: {exc}"
                ok = False
            wall = time.perf_counter() - t0
            # the objective step resets the counters between passes, so
            # its compile figures cover its last passes only
            snap = obs.counters_snapshot()
            rows.append({
                "step": name, "ok": err is None, "error": err,
                "smoke_wall_s": round(wall, 1),
                "smoke_compile_s": round(snap.get("compile_secs", 0.0), 1),
                # compile REQUESTS: a persistent-cache hit counts here too
                "compile_requests": int(snap.get("recompiles", 0)),
                "cache_hits": cache.hits - h0,
                "cache_misses": cache.misses - m0,
                "facts": facts,
            })
            print(f"  {'PASS' if err is None else 'FAIL'} {name}: "
                  f"{wall:.1f}s wall", flush=True)
        state.clear()

    print("-- chip_smoke summary (smoke timings of single cold calls, "
          "compilation included — not benchmark figures) --")
    for r in rows:
        print(json.dumps(r, default=str))
    print(json.dumps({"peak_bytes_in_use": _peak_memory(),
                      "cache_hits": cache.hits,
                      "cache_misses": cache.misses}))
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"chip_smoke_{dev['count']}chip.json"),
              "w") as f:
        json.dump({"device": dev, "steps": rows}, f, indent=1, default=str)
    if not ok:
        sys.exit("chip_smoke: FAILED — " + "; ".join(
            f"{r['step']}: {r['error']}" for r in rows if not r["ok"]))
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
