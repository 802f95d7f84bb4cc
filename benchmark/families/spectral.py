"""Driver for the ``spectral`` family (``SpectralClustering`` at upstream's
defaults — Nyström, rbf, 100 landmarks, 8 clusters, 10 restarts — on a
resident, row-sharded X): the family's own seeded generator, one fit and
the read of all its labels, what must have engaged, and the comparison with
the plain reference (``references/spectral.py``) that decides ``correct``;
``tolerances_spectral.py`` gives every limit beside its reason."""

from __future__ import annotations

import inspect

import numpy as np

from benchmark import datagen
from benchmark import tolerances_spectral as T
from benchmark.families import _common as C
from benchmark.references import spectral as ref


# -- the data: equal-weight Gaussian groups off the origin ---------------------

def mixture_params(rng, d, p):
    """Host parameters: ``components`` group centres drawn at
    ``center_scale`` a coordinate and ONE offset, common to every row, drawn
    at ``offset_scale`` a coordinate."""
    k = int(p["components"])
    return {"centers": (float(p["center_scale"])
                        * rng.standard_normal((k, d))).astype(np.float32),
            "offset": (float(p["offset_scale"])
                       * rng.standard_normal(d)).astype(np.float32)}


def mixture_rows(key, rows, d, hp, p):
    """``offset + centers[group] + noise_scale * z`` with the group uniform
    over the components, and the group itself (float32) beside the rows:
    only the check reads it. The centre is selected by f32 multiplies
    (``HIGHEST``), so the rows are the same numbers at any backend's default
    precision."""
    import jax
    import jax.numpy as jnp

    kl, kx = jax.random.split(key)
    k = hp["centers"].shape[0]
    group = jax.random.randint(kl, (rows,), 0, k)
    means = jnp.dot(jax.nn.one_hot(group, k, dtype=jnp.float32),
                    hp["centers"], precision=jax.lax.Precision.HIGHEST)
    X = hp["offset"] + means + float(p["noise_scale"]) * jax.random.normal(
        kx, (rows, d), jnp.float32)
    return X, group.astype(jnp.float32)


# the benchmark's one table of distributions gains this family's; the
# born-sharded, chunked program in datagen.py then serves it as the others
datagen.GENERATORS.setdefault("spectral_mixture",
                              (mixture_params, mixture_rows))


def make_data(cfg, traffic, chips, seed, mesh):
    """The cell's rows, placed as every family's; ``y`` is each row's group.
    A program from before PR 38 (an eager fit with no ``landmarks_``, no
    ``solver_info_``, no ``spectral.embed`` program; at this size its eager
    (n, 100) buffers do not fit the chip) cannot run this family's cells:
    say so before any data is made."""
    from dask_ml_tpu.models import spectral

    if not hasattr(spectral, "NYSTROM_JITTER"):
        from benchmark.harness import BenchmarkError

        raise BenchmarkError(
            "this program's SpectralClustering records no landmarks_, no "
            "solver_info_ and no spectral.embed program (it is from before "
            "PR 38): the spectral cells cannot run on it")
    return C.place(cfg, traffic, chips, seed, mesh)


# -- one cycle -----------------------------------------------------------------

def vary(cell, data, k):
    """Every fit draws new landmarks and new restarts: the cycle's
    ``random_state``."""
    data["random_state"] = (int(data["seed"]) * 7919 + int(k)) % (2**31 - 1)


# in the CPU rehearsal the choice the TPU's auto-gate makes for the restarts
# is REQUESTED: the fused Lloyd kernel (the program then interprets it)
_REHEARSAL_KMEANS = {"use_pallas": True}


def make_estimator(cell, data, interpret):
    extra = {"kmeans_params": dict(_REHEARSAL_KMEANS)} if interpret else {}
    return C.new_estimator(cell.config, random_state=data["random_state"],
                           **extra)


def fit(est, data):
    """Ends with ``labels_`` ready on the device (the winner's labels pass
    is waited for inside the fit; this wait finds it done)."""
    import jax

    est.fit(data["X"])
    jax.block_until_ready(est.labels_.data)


def predict(est, data):
    """What ``fit_predict`` hands a user, read: the labels of ALL rows as a
    host int32 array."""
    return np.asarray(est.labels_.to_numpy(), np.int32)


def fit_facts(est):
    info = est.solver_info_
    return {"n_iter": int(info["lloyd_iters"]),
            "restarts": int(info["restarts"]),
            "assign_fused": bool(info["assign_fused"])}


def stated_defaults(cfg):
    """Failures where a signature default of ``SpectralClustering`` or of
    ``KMeans`` is not the stated one, or a constant the reference restates
    is not the program's."""
    from dask_ml_tpu.cluster import KMeans, SpectralClustering
    from dask_ml_tpu.models import spectral

    out = []
    stated = dict(cfg["estimator"]["params"], persist_embedding=False)
    for cls, want in ((SpectralClustering, stated),
                      (KMeans, {k: v for k, v in
                                cfg["kmeans_defaults"].items()
                                if k != "note"})):
        sig = inspect.signature(cls.__init__).parameters
        for k, v in want.items():
            have = sig[k].default if k in sig else "<no such parameter>"
            if have != v:
                out.append(f"{cls.__name__}'s default {k} is {have!r}, the "
                           f"configuration states {v!r}")
    for name in ("NYSTROM_JITTER", "TINY"):
        if getattr(spectral, name, None) != getattr(ref, name):
            out.append(f"models/spectral.py's {name} is "
                       f"{getattr(spectral, name, None)!r}, the reference "
                       f"restates {getattr(ref, name)!r}")
    return out


def engaged(cell, est, data, programs=None):
    """What must have carried the fit; a fallback is a failure."""
    chk = C.Check()
    want = cell.config["expect"]
    p = cell.config["estimator"]["params"]
    info = dict(getattr(est, "solver_info_", {}))
    for k, v in p.items():
        if k == "kmeans_params":
            chk.need(est.kmeans_params in (v, _REHEARSAL_KMEANS),
                     f"kmeans_params {est.kmeans_params!r}: the cell runs "
                     f"KMeans' defaults")
        else:
            chk.need(getattr(est, k) == v, f"the estimator's {k} is "
                                           f"{getattr(est, k)!r}, not {v!r}")
    for msg in stated_defaults(cell.config):
        chk.need(False, msg)
    chk.need(getattr(est, "fit_dtype_", None) == want["fit_dtype"],
             f"fit_dtype_ is {getattr(est, 'fit_dtype_', None)!r}, "
             f"not {want['fit_dtype']!r}")
    for k in ("embed", "precision"):
        chk.need(info.get(k) == want[k],
                 f"solver_info_[{k!r}] is {info.get(k)!r}, not {want[k]!r}")
    chk.need(info.get("qr_fallbacks") == 0,
             f"the tall QR fell back to Householder: qr_fallbacks "
             f"{info.get('qr_fallbacks')!r}")
    chk.need(info.get("restarts") == p["n_init"],
             f"{info.get('restarts')!r} restarts, not {p['n_init']}")
    on = len(data["X"].data.sharding.device_set)
    chk.need(on == data["chips"], f"X lives on {on} of {data['chips']} chips")
    if programs is not None:
        for name, calls in want["programs"].items():
            chk.need(programs.get(name) == calls,
                     f"{name} ran {programs.get(name)} times in the fit, "
                     f"not {calls}: {programs}")
        lloyd = sum(programs.get(n, 0) for n in want["lloyd_programs"])
        chk.need(lloyd == p["n_init"],
                 f"KMeans' Lloyd program ran {lloyd} times in the fit, not "
                 f"{p['n_init']}: {programs}")
    chk.facts.update(assign_fused=info.get("assign_fused"),
                     lloyd_iters=info.get("lloyd_iters"))
    return chk


# -- the check -----------------------------------------------------------------

def outputs(est, predicted, n):
    """What the check reads of a fitted estimator, as plain host values (the
    control is handed in in the same shape)."""
    info = est.solver_info_
    return {"E": np.asarray(est.embedding_.to_numpy(), np.float32)[:n],
            "singular_values": np.asarray(est.eigenvalues_, np.float64),
            "labels": predicted,
            "inertias": list(info["inertias"]), "winner": info["winner"]}


def control_outputs(ctl, groups, k):
    """A reference run ``ctl`` (the control's) in :func:`outputs`' shape; its
    labels are its own nearest-point labels on the rows ``groups`` covers."""
    m = len(groups)
    labels = ref.nearest_point(
        ctl["E"][:m], ref.cluster_points(ctl["E"][:m], groups, k))
    return {"E": ctl["E"], "singular_values": ctl["singular_values"][:k],
            "labels": labels.astype(np.int32), "inertias": [0.0],
            "winner": 0}


def check(cell, est, data, predicted):
    """The last fitted estimator against the reference's embedding of ALL
    the cell's rows from the SAME landmark rows, and the labels the
    ``predict`` step read against the reference's on the sample rows; then
    the ``bf16`` control, handed in as the program's outputs are, must fail
    the same limits."""
    chk = C.Check()
    facts = chk.facts
    p = cell.config["estimator"]["params"]
    k, c = int(p["n_clusters"]), int(p["n_components"])
    n = data["n_rows"]
    m = min(int(cell.traffic["sample_rows"]), n)
    if cell.traffic["check_rows"] != "all":
        raise ValueError("the spectral check embeds all rows")

    # (A) the landmarks
    idx = np.asarray(getattr(est, "landmarks_", ()), np.int64)
    ok = chk.need(idx.shape == (min(c, n),) and len(set(idx.tolist())) == len(idx)
                  and idx.min() >= 0 and idx.max() < n,
                  f"landmarks_ are not {min(c, n)} distinct rows of the {n}: "
                  f"shape {idx.shape}")
    emb = getattr(est, "embedding_", None)
    ok = ok and chk.need(
        emb is not None and emb.shape == (n, k)
        and isinstance(predicted, np.ndarray) and predicted.shape == (n,)
        and predicted.dtype == np.int32,
        f"embedding_ of shape {getattr(emb, 'shape', None)} / labels "
        f"{getattr(predicted, 'shape', None)} "
        f"{getattr(predicted, 'dtype', None)}")
    if not ok:
        return chk

    blocks = ref.shard_blocks(data["X"].data, n)
    groups = np.asarray(C.device_rows(data["y"], m)).astype(np.int64)
    want = ref.embedding(blocks, idx, p["gamma"], k)
    facts.update(check_rows=want["n"], sample_rows=m,
                 singular_value_k=float(want["singular_values"][k - 1]),
                 singular_value_k1=float(want["singular_values"][k]),
                 singular_gap=want["gap"])
    chk.need(want["n"] == n, f"{want['n']} reference rows for {n}")
    chk.need(want["gap"] >= T.MIN_GAP,
             f"the reference's own spectrum leaves a gap of {want['gap']:.3f}"
             f" after the {k}th singular value: the subspace is ill-defined")
    ref_labels = ref.nearest_point(
        want["E"][:m], ref.cluster_points(want["E"][:m], groups, k))

    out = outputs(est, predicted, n)
    chk.need(np.isfinite(out["E"]).all(), "non-finite embedding_")
    for name, (value, limit) in T.readings(out, want, ref_labels, m,
                                           k).items():
        facts[name], facts[name + "_limit"] = value, limit
        chk.need(value <= limit, f"{name}: {value:.3e} > {limit:.3e}")

    # (E) the control: the affinity by the expansion with a bf16 cross term
    ctl_out = control_outputs(
        ref.embedding(blocks, idx, p["gamma"], k, cross="bf16"), groups, k)
    failed = []
    for name, (value, limit) in T.readings(ctl_out, want, ref_labels, m,
                                           k).items():
        facts["control_" + name] = value
        if not value <= limit:
            failed.append(name)
    facts["control_fails"] = failed
    chk.need(failed, "the bf16-cross control passes every limit: the check "
                     "cannot tell the stated precision from a lower one")
    return chk
