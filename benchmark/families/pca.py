"""Driver for the ``pca`` family (``PCA(svd_solver="randomized")`` on a
resident, row-sharded X): the family's own seeded generator, one fit and one
``transform``, what must have engaged, and the comparison with the exact
reference that decides ``correct``."""

from __future__ import annotations

import numpy as np

from benchmark import datagen
from benchmark import tolerances_pca as T
from benchmark.families import _common as C
from benchmark.references import pca as ref


# -- the data: a planted subspace over isotropic noise -------------------------

def planted_params(rng, d, p):
    """Host parameters: ``components`` orthonormal directions, their
    eigenvalues falling geometrically from ``eigen_top`` to ``eigen_bottom``
    (the noise's unit variance included), and a non-zero mean."""
    k = int(p["components"])
    top, bottom = float(p["eigen_top"]), float(p["eigen_bottom"])
    lam = top * (bottom / top) ** (np.arange(k) / max(k - 1, 1))
    basis = np.linalg.qr(rng.standard_normal((d, k)))[0]
    return {"basis": basis.astype(np.float32),
            "scale": np.sqrt(lam - 1.0).astype(np.float32),
            "mean": (float(p["mean_scale"])
                     * rng.standard_normal(d)).astype(np.float32)}


def planted_rows(key, rows, d, hp, p):
    """``mean + (z * scale) @ basis^T + noise``: covariance
    ``I + basis diag(scale^2) basis^T``. f32 multiplies (``HIGHEST``), so
    the rows are the same numbers on any backend's default precision."""
    import jax
    import jax.numpy as jnp

    kz, ke = jax.random.split(key)
    z = jax.random.normal(kz, (rows, hp["scale"].shape[0]), jnp.float32)
    low = jnp.dot(z * hp["scale"], hp["basis"].T,
                  precision=jax.lax.Precision.HIGHEST)
    return hp["mean"] + low + jax.random.normal(ke, (rows, d),
                                                jnp.float32), None


# the benchmark's one table of distributions gains this family's; the
# born-sharded, chunked program in datagen.py then serves it as the others
datagen.GENERATORS.setdefault("planted_subspace",
                              (planted_params, planted_rows))



def make_data(cfg, traffic, chips, seed, mesh):
    """The cell's rows, placed as every family's. A program from before the
    resident PCA recorded what carried its fit (``solver_info_``, the
    ``pca.rsvd`` program, ``x_sweeps``: PR 25) cannot run this family's
    cells: say so before any data is made."""
    from dask_ml_tpu.ops import linalg

    if not hasattr(linalg, "randomized_svd_sweeps"):
        from benchmark.harness import BenchmarkError

        raise BenchmarkError(
            "this program's resident PCA records no solver_info_, x_sweeps "
            "or pca.rsvd program (it is from before PR 25): the pca cells "
            "cannot run on it")
    return C.place(cfg, traffic, chips, seed, mesh)


# -- one cycle -----------------------------------------------------------------

def vary(cell, data, k):
    """Every fit draws a new sketch: the cycle's ``random_state``. The work
    does not depend on it."""
    data["random_state"] = (int(data["seed"]) * 7919 + int(k)) % (2**31 - 1)


def make_estimator(cell, data, interpret):
    return C.new_estimator(cell.config, random_state=data["random_state"])


def fit(est, data):
    """Ends with ``components_`` on the host, as the API leaves them."""
    est.fit(data["X"])


def predict(est, data):
    """``transform`` over the whole X; ends when the device scores are
    ready."""
    import jax

    out = est.transform(data["X"])
    jax.block_until_ready(out.data)
    return out


def fit_facts(est):
    info = est.solver_info_
    return {"n_iter": int(info["n_iter"]), "x_sweeps": int(info["x_sweeps"])}


def engaged(cell, est, data, programs=None):
    """What must have carried the fit; a fallback is a failure."""
    chk = C.Check()
    want = cell.config["expect"]
    info = dict(getattr(est, "solver_info_", {}))
    chk.need(info.get("solver") == want["solver"],
             f"solver is {info.get('solver')!r}, not {want['solver']!r}")
    chk.need(getattr(est, "fit_dtype_", None) == want["fit_dtype"],
             f"fit_dtype_ is {getattr(est, 'fit_dtype_', None)!r}, "
             f"not {want['fit_dtype']!r}")
    on = len(data["X"].data.sharding.device_set)
    chk.need(on == data["chips"], f"X lives on {on} of {data['chips']} chips")
    if programs is not None:
        chk.need(programs.get(want["program"], 0) >= 1,
                 f"program {want['program']!r} did not run in the fit: "
                 f"{programs}")
    return chk


# -- the check -----------------------------------------------------------------

def check(cell, est, data, predicted):
    """The last fitted PCA against the EXACT decomposition of the
    covariance of all the cell's rows, and its last ``transform`` on the
    sample rows; ``tolerances_pca.py`` gives every band."""
    chk = C.Check()
    facts = chk.facts
    k = int(cell.config["estimator"]["params"]["n_components"])
    n, d = data["n_rows"], data["d"]
    if cell.traffic["check_rows"] != "all":
        raise ValueError("the pca check takes the covariance of all rows")
    exact = ref.pca_exact(ref.shard_blocks(data["X"].data), k)
    info = est.solver_info_
    facts.update(check_rows=exact["n"],
                 eigen_gap=float(exact["eigenvalues"][k - 1]
                                 / exact["eigenvalues"][k]))
    ok = chk.need(exact["n"] == n and est.components_.shape == (k, d)
                  and np.isfinite(est.components_).all(),
                  f"components_ shape {est.components_.shape} / non-finite, "
                  f"or {exact['n']} reference rows for {n}")
    if ok:
        for name, (value, band) in T.readings(
                exact, est.mean_, est.components_, est.explained_variance_,
                est.explained_variance_ratio_, info["size"],
                info["n_iter"]).items():
            facts[name], facts[name + "_band"] = value, band
            chk.need(value <= band, f"{name}: {value:.3e} > {band:.3e}")

    m = min(int(cell.traffic["sample_rows"]), n)
    scores = getattr(predicted, "data", None)
    ok = chk.need(scores is not None and scores.shape[1] == k
                  and predicted.n_rows == n,
                  f"transform returned {type(predicted).__name__} of shape "
                  f"{getattr(scores, 'shape', None)}")
    if ok:
        want = ref.transform(C.device_rows(data["X"], m), est.mean_,
                             est.components_)
        got = np.asarray(C.device_rows(predicted, m))
        err = T.transform_reading(got, want)
        facts.update(sample_rows=m, transform=err)
        chk.need(np.isfinite(got).all() and err <= T.TOL_TRANSFORM,
                 f"transform off by {err:.3e} of the score scale "
                 f"(band {T.TOL_TRANSFORM:.0e})")
    return chk
