"""Headline benchmark: LogisticRegression.fit throughput on device.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Metric: samples/sec/chip processed by the device-resident L-BFGS fit
(counting one full data pass per outer iteration — line-search passes are
not counted, so this undercounts true throughput). vs_baseline is the ratio
against scikit-learn's lbfgs LogisticRegression measured the same way on a
subsample on this host's CPU — the reference's per-block compute engine
(SURVEY.md §6: no published in-repo numbers; BASELINE.json configs[0]).

Data is generated ON DEVICE (jax.random) and stays there: the benchmark
measures the compute path, not the host→device copy.

One process, on whatever backend jax gives it: no probe, no child that
reruns elsewhere, no fallback. Every row names the device it ran on
(``device``: platform, device_kind, count) and the design-matrix dtype,
a section that fails is reported with its error AND makes the run exit
non-zero, and a deadline thread (BENCH_TOTAL_TIMEOUT) emits whatever
finished, marked truncated, and exits non-zero. A number from a CPU run
is a CPU number — read the ``device`` field.
"""

import json
import os
import subprocess
import sys
import threading
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

# Self-watchdog: emit the JSON line ourselves rather than letting an
# external timeout kill us output-less.
_TOTAL_TIMEOUT = float(os.environ.get("BENCH_TOTAL_TIMEOUT", "1500"))


def _mfu_fields(model_flops, elapsed, n_chips, peak):
    """Achieved model FLOP/s and MFU vs per-chip peak (analytic
    model_flops; see observability/_peak.py). ``peak`` is None on a
    device without a published peak (any CPU run): MFU is then "not
    measured", never a ratio against a number timed on the spot."""
    if peak is None:
        return {"model_flops": round(model_flops), "mfu": "not measured"}
    from dask_ml_tpu.observability._peak import mfu_fields

    return mfu_fields(model_flops, elapsed, n_chips, peak)


def _print_stall(rec):
    """Watchdog stall dump -> stderr (the JSON stdout line must stay
    clean): the stalled span plus its thread's stack."""
    lines = [f"bench watchdog: span {rec.get('span')!r} open "
             f"{rec.get('age_s')}s on thread {rec.get('thread')!r}"]
    lines.extend(rec.get("stalled_stack", [])[-8:])
    sys.stderr.write("\n".join(lines) + "\n")


def run():
    import dask_ml_tpu  # noqa: F401  (places the compile cache first)
    import jax
    import jax.numpy as jnp

    backend = jax.default_backend()
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices())}

    # span-level stall watchdog (observability/_watchdog.py): any span
    # (fit, stream pass, serving batch) open past the deadline dumps
    # all-thread tracebacks + device memory gauges to stderr while the
    # bench keeps running — the in-flight diagnostics the deadline
    # thread (which only bounds TOTAL time) cannot give. Daemon thread.
    from dask_ml_tpu.observability import Watchdog
    from dask_ml_tpu.observability.live import ensure_telemetry

    Watchdog(
        float(os.environ.get("BENCH_WATCHDOG_TIMEOUT", "120")),
        on_stall=_print_stall,
    ).start()
    # live exporter (DASK_ML_TPU_OBS_HTTP_PORT): during a hung run
    # an operator can curl /status for the open-span stack instead of
    # waiting on the watchdog's one-shot dump; no-op when the env knob
    # is unset, so the timed fits below keep their profile
    ensure_telemetry()
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.parallel import as_sharded

    n_chips = len(jax.devices())
    on_tpu = backend == "tpu"
    n_rows = 4_000_000 if on_tpu else 200_000
    n_feat = 256 if on_tpu else 64

    key = jax.random.PRNGKey(0)
    kb, kx, ky = jax.random.split(key, 3)
    beta_true = jax.random.normal(kb, (n_feat,)) / np.sqrt(n_feat)

    @jax.jit
    def gen():
        X = jax.random.normal(kx, (n_rows, n_feat), jnp.float32)
        p = jax.nn.sigmoid(X @ beta_true)
        y = (jax.random.uniform(ky, (n_rows,)) < p).astype(jnp.float32)
        return X, y

    X, y = jax.block_until_ready(gen())
    Xs, ys = as_sharded(X), as_sharded(y)

    max_iter = 50
    from dask_ml_tpu import config

    # bf16 design matrix on TPU: higher MXU throughput, measured identical
    # converged coef error/score vs f32 on this problem (solver state and
    # accumulation stay f32). dtype is recorded in the JSON so the ratio
    # is attributable.
    dtype = "bfloat16" if on_tpu else "float32"
    with config.set(dtype=dtype):
        # warm the compile cache AT FULL SHAPE (XLA programs are
        # shape-specialized) with a 1-iteration fit
        LogisticRegression(solver="lbfgs", max_iter=1, tol=0.0).fit(Xs, ys)

        t0 = time.perf_counter()
        clf = LogisticRegression(solver="lbfgs", max_iter=max_iter, tol=0.0)
        clf.fit(Xs, ys)
        elapsed = time.perf_counter() - t0
    iters = clf.n_iter_ or max_iter

    # traceability run (BASELINE.md measurement protocol): a SEPARATE
    # short fit writes per-iteration JSONL. The timed fit above runs
    # WITHOUT logging — the log=True trace carries a per-iteration host
    # callback that would pollute the headline number.
    metrics_file = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_metrics.jsonl"
    )
    open(metrics_file, "w").close()  # fresh file per bench run
    # program tracking ON for the traceability fit only: the recorded
    # JSONL carries per-program compile/FLOP/HBM attribution (and the
    # fit span a ctr_program_flops delta -> measured MFU in the report
    # CLI) as a cross-check of the analytic logreg_flops below. The
    # TIMED fit above ran without it — the registry's analysis pass
    # costs one extra AOT compile per program.
    from dask_ml_tpu.observability import (MetricsLogger, log_programs,
                                           programs_reset)

    programs_reset()
    with config.set(dtype=dtype, metrics_path=metrics_file,
                    obs_programs=True):
        LogisticRegression(solver="lbfgs", max_iter=10, tol=0.0).fit(Xs, ys)
        # tiny STREAMED fits under program tracking so the report CLI's
        # programs table ranks the streamed super-block kernels — the
        # XLA flavors (superblock.*) here on CPU, the fused Pallas
        # flavors (pallas.sgd_step / pallas.glm_vgh /
        # pallas.kmeans_stream) on real TPU — against the resident
        # programs (ISSUE 8: MFU-ranked kernel attribution)
        from dask_ml_tpu.cluster import KMeans as _KM
        from dask_ml_tpu.models.sgd import SGDClassifier as _SGD

        _rs = np.random.RandomState(3)
        _Xs = _rs.randn(16_384, 32).astype(np.float32)
        _ys = (_Xs[:, 0] > 0).astype(np.float32)
        with config.set(stream_block_rows=2048):   # 128-multiple:
            # the fused streamed kernels' grid requirement
            _SGD(max_iter=1, random_state=0,
                 shuffle=False).fit(_Xs, _ys)
            LogisticRegression(solver="lbfgs", max_iter=3).fit(
                _Xs, _ys
            )
            _KM(n_clusters=4, random_state=0, max_iter=2,
                init="random").fit(_Xs)
        with MetricsLogger(metrics_file) as _lg:
            log_programs(_lg)
    value = n_rows * iters / elapsed / n_chips
    peak = None
    if on_tpu:
        from dask_ml_tpu.observability._peak import resolve_peak

        peak = resolve_peak()  # an unknown device_kind raises
    # lbfgs data pass: eta = X@beta (2nd) + grad = X.T@resid (2nd) per
    # counted iteration; line-search passes uncounted (consistent with
    # the samples metric, so mfu undercounts like it does)
    logreg_flops = 4.0 * n_rows * n_feat * iters

    # sklearn reference on a host subsample of the same data
    from sklearn.linear_model import LogisticRegression as SkLR

    sub = min(n_rows, 100_000)
    Xh = np.asarray(X[:sub])
    yh = np.asarray(y[:sub])
    sk = SkLR(solver="lbfgs", max_iter=max_iter, tol=0.0)
    t0 = time.perf_counter()
    sk.fit(Xh, yh)
    sk_elapsed = time.perf_counter() - t0
    sk_iters = int(np.max(sk.n_iter_)) or max_iter
    sk_value = sub * sk_iters / sk_elapsed

    result = {
        "metric": "logreg_fit_samples_per_sec_per_chip",
        "value": round(value, 1),
        "unit": "samples/s/chip",
        "vs_baseline": round(value / sk_value, 3),
        "backend": backend,
        "device": device,
        "dtype": dtype,
        "n_chips": n_chips,
        "n_rows": n_rows,
        "n_features": n_feat,
        "iters": int(iters),
        # the baseline side of the ratio, spelled out: sklearn lbfgs on a
        # host subsample of the SAME data, normalized per sample per
        # counted iteration — so the ratio compares per-sample throughput,
        # not absolute wall clock at mismatched sizes
        "baseline": {
            "what": "sklearn LogisticRegression(lbfgs) on this host's CPU",
            "n_rows": int(sub),
            "iters": int(sk_iters),
            "samples_per_sec": round(sk_value, 1),
        },
        "metrics_file": metrics_file,
        **_mfu_fields(logreg_flops, elapsed, n_chips, peak),
    }
    # secondary BASELINE configs (VERDICT r2 #6). A section that fails
    # is recorded as an error row (traceback on stderr), the remaining
    # sections still run — a chip run is too dear to lose to its first
    # failure — and main() exits non-zero for any error row.
    # The headline + each completed extra land in _partial as they finish
    # so the deadline thread can emit real numbers on an overrun.
    _partial["result"] = result
    extras = _partial["extras"]

    def _try(fn, *args):
        try:
            out = fn(*args)
        except Exception as exc:
            traceback.print_exc(file=sys.stderr)
            out = {"metric": fn.__name__, "value": None,
                   "error": f"{type(exc).__name__}: {exc}"}
        # a section may return several metric entries (fleet does)
        for entry in out if isinstance(out, list) else [out]:
            extras.append(dict(entry, device=device))

    # the extras run under an EXPLICIT f32 default: their recorded
    # metrics are labeled dtype="float32", and the config.dtype="auto"
    # policy (bf16 on TPU since ISSUE 8) must not silently change what
    # a recorded series measures. Sections that time bf16 on purpose
    # (kmeans_bf16 / logreg_bf16 / the streamed bf16 flavor) set
    # dtype="bfloat16" internally, which nests OVER this pin.
    with config.set(dtype="float32"):
        _try(_bench_logreg_f32, jax, on_tpu, n_chips, Xs, ys)
        # free the headline design matrix BEFORE the kmeans/rsvd
        # configs — holding its HBM alongside their working sets OOMs
        # a 16G chip
        del Xs, ys, X, y
        _try(_bench_kmeans, jax, on_tpu, n_chips, peak)
        _try(_bench_kmeans_bf16, jax, on_tpu, n_chips, peak)
        _try(_bench_logreg_bf16, jax, on_tpu, n_chips, peak)
        _try(_bench_rsvd, jax, on_tpu, n_chips, peak)
        _try(_bench_incremental_sgd, jax, on_tpu, n_chips, peak)
        _try(_bench_streamed_sgd, jax, on_tpu, n_chips, peak)
        _try(_bench_sharded_streaming, jax, on_tpu, n_chips)
        _try(_bench_fused_sharded_stream, jax, on_tpu, n_chips)
        _try(_bench_sparse_stream, jax, on_tpu, n_chips)
        _try(_bench_feature_sharded, jax, on_tpu, n_chips)
        _try(_bench_hyperband, jax, on_tpu, n_chips)
        _try(_bench_c_grid_search, jax, on_tpu, n_chips)
        _try(_bench_serving, jax, on_tpu, n_chips)
        _try(_bench_int8_serving, jax, on_tpu, n_chips)
        _try(_bench_fleet, jax, on_tpu, n_chips)
        _try(_bench_drift, jax, on_tpu, n_chips)
        _try(_bench_request_trace, jax, on_tpu, n_chips)
        _try(_bench_federation, jax, on_tpu, n_chips)
        _try(_bench_fleet_observability, jax, on_tpu, n_chips)
        _try(_bench_incident_plane, jax, on_tpu, n_chips)
    result["extra_metrics"] = extras
    # every successful metric also APPENDS to BENCH_floors.jsonl (run
    # marker + one kind="bench_metric" record each; the file is never
    # truncated, unlike the per-run BENCH_metrics.jsonl trace):
    # scripts/bench_sentinel.py seeds budget floors for metrics no
    # recorded round carries yet from the runs BEFORE the newest one —
    # so the *_bf16 / *_int8 flavors recorded in the session that added
    # them gate the very first round that lands them
    try:
        floors_file = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_floors.jsonl",
        )
        with open(floors_file, "a") as fh:
            fh.write(json.dumps(
                {"kind": "bench_run_start", "t": time.time(),
                 "backend": backend}
            ) + "\n")
            for entry in [result] + extras:
                if entry.get("metric") and entry.get("value") is not None:
                    fh.write(json.dumps({
                        "kind": "bench_metric",
                        "metric": entry["metric"],
                        "value": entry["value"],
                        "unit": entry.get("unit", ""),
                        "backend": entry.get("backend"),
                    }) + "\n")
    except Exception:
        pass
    return result


def _bench_c_grid_search(jax, on_tpu, n_chips):
    """GridSearchCV over a pure-C logreg grid: the stacked-lam fast path
    (all candidates in one compiled solve per fold) vs the general
    per-candidate path (same fits, forced by an extra constant grid
    key). Reports both so the speedup is on record per backend."""
    import time

    import jax.numpy as jnp

    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.model_selection import GridSearchCV
    from dask_ml_tpu.parallel import as_sharded

    n = 1_000_000 if on_tpu else 100_000
    d = 64
    key = jax.random.PRNGKey(5)

    @jax.jit
    def gen():
        kx, ky = jax.random.split(key)
        X = jax.random.normal(kx, (n, d), jnp.float32)
        y = (X[:, 0] + 0.5 * jax.random.normal(ky, (n,)) > 0).astype(
            jnp.float32
        )
        return X, y

    X, y = jax.block_until_ready(gen())
    Xs, ys = as_sharded(X), as_sharded(y)
    Cs = [10.0 ** e for e in range(-4, 4)]

    def run(params):
        s = GridSearchCV(
            LogisticRegression(solver="lbfgs", max_iter=20, tol=0.0),
            params, cv=2, refit=False, scheduler="synchronous",
        )
        s.fit(Xs, ys)
        return s

    run({"C": Cs})  # compile warmup
    t0 = time.perf_counter()
    fast = run({"C": Cs})
    t_fast = time.perf_counter() - t0
    # fail BEFORE paying for the general-path runs, and with a real
    # raise (assert vanishes under -O): a silent fallback would label
    # general-path timing as the fast path
    if getattr(fast, "_c_grid_vmapped_", None) != len(Cs):
        raise RuntimeError(
            "C-grid fast path not taken: "
            f"{getattr(fast, '_c_grid_fallback_', 'ineligible')}"
        )
    general = {"C": Cs, "intercept_scaling": [1.0]}
    run(general)
    t0 = time.perf_counter()
    run(general)
    t_general = time.perf_counter() - t0
    return {
        "metric": "c_grid_search_seconds",
        "value": round(t_fast, 3),
        "unit": "s",
        "backend": jax.default_backend(),
        "dtype": "float32",
        "n_rows": n,
        "n_features": d,
        "n_candidates": len(Cs),
        "cv": 2,
        "general_path_seconds": round(t_general, 3),
        "speedup_vs_general": round(t_general / t_fast, 3),
    }


def _bench_logreg_f32(jax, on_tpu, n_chips, Xs, ys):
    """f32 point for the SAME headline fit so the bf16 contribution is
    attributable (ADVICE r1 #3). Skipped-on-CPU is impossible: on CPU the
    headline IS f32, so this just re-measures at fewer iterations."""
    import time

    from dask_ml_tpu import config
    from dask_ml_tpu.linear_model import LogisticRegression

    max_iter = 20
    with config.set(dtype="float32"):
        LogisticRegression(solver="lbfgs", max_iter=1, tol=0.0).fit(Xs, ys)
        t0 = time.perf_counter()
        clf = LogisticRegression(solver="lbfgs", max_iter=max_iter,
                                 tol=0.0).fit(Xs, ys)
        elapsed = time.perf_counter() - t0
    iters = clf.n_iter_ or max_iter
    return {
        "metric": "logreg_fit_samples_per_sec_per_chip_f32",
        "value": round(Xs.n_rows * iters / elapsed / n_chips, 1),
        "unit": "samples/s/chip",
        "backend": jax.default_backend(),
        "dtype": "float32",
        "n_rows": Xs.n_rows,
        "iters": int(iters),
    }


def _bench_kmeans(jax, on_tpu, n_chips, peak):
    """BASELINE configs[1]: KMeans (k=64) Lloyd iterations/sec. d=128
    keeps the lane dimension at the TPU tile width (d=64 would pad 2x in
    HBM)."""
    import time

    import jax.numpy as jnp

    from dask_ml_tpu.cluster import KMeans
    from dask_ml_tpu.parallel import as_sharded

    n = 8_000_000 if on_tpu else 100_000
    d, k, iters = 128, 64, 10
    key = jax.random.PRNGKey(1)

    @jax.jit
    def gen():
        return jax.random.normal(key, (n, d), jnp.float32)

    X = as_sharded(jax.block_until_ready(gen()))
    init = np.asarray(X.data[:k])
    km = KMeans(n_clusters=k, init=init, max_iter=2, tol=0.0)
    km.fit(X)  # compile warmup at full shape
    t0 = time.perf_counter()
    km = KMeans(n_clusters=k, init=init, max_iter=iters, tol=0.0)
    km.fit(X)
    elapsed = time.perf_counter() - t0
    return {
        "metric": "kmeans_lloyd_iterations_per_sec",
        "value": round(km.n_iter_ / elapsed, 3),
        "unit": "iterations/s",
        "backend": jax.default_backend(),
        "dtype": "float32",
        "n_rows": n,
        "n_features": d,
        "k": k,
        "samples_per_sec_per_chip": round(n * km.n_iter_ / elapsed / n_chips, 1),
        # distance matmul only (2ndk per Lloyd iteration) — a lower bound
        # that excludes the assignment reduce and center accumulation
        **_mfu_fields(2.0 * n * d * k * km.n_iter_, elapsed, n_chips, peak),
    }


def _bench_kmeans_bf16(jax, on_tpu, n_chips, peak):
    """KMeans with config.dtype='bfloat16': the Lloyd distance matmul at
    bf16/f32-accumulation (VERDICT r4 missing #5 — the bf16 policy now
    reaches past the GLMs). On CPU bf16 is emulated and SLOWER — the
    line exists so both dtypes are always on record; TPU is where the
    2x MXU rate shows."""
    import time

    import jax.numpy as jnp

    from dask_ml_tpu import config
    from dask_ml_tpu.cluster import KMeans
    from dask_ml_tpu.parallel import as_sharded

    n = 8_000_000 if on_tpu else 100_000
    d, k, iters = 128, 64, 10
    key = jax.random.PRNGKey(1)

    @jax.jit
    def gen():
        return jax.random.normal(key, (n, d), jnp.float32)

    X = as_sharded(jax.block_until_ready(gen()))
    init = np.asarray(X.data[:k])

    def timed(dtype):
        # BOTH dtypes on the XLA path (use_pallas=False): the headline
        # f32 line may use the Pallas kernel on TPU, so this pair — not
        # that line — isolates the dtype effect from the kernel choice
        with config.set(dtype=dtype):
            KMeans(n_clusters=k, init=init, max_iter=2, tol=0.0,
                   use_pallas=False).fit(X)
            km = KMeans(n_clusters=k, init=init, max_iter=iters,
                        tol=0.0, use_pallas=False)
            t0 = time.perf_counter()
            km.fit(X)
            return km.n_iter_, time.perf_counter() - t0

    it_f32, el_f32 = timed("float32")
    it_b16, el_b16 = timed("bfloat16")
    return {
        "metric": "kmeans_lloyd_iterations_per_sec_bf16",
        "value": round(it_b16 / el_b16, 3),
        "unit": "iterations/s",
        "backend": jax.default_backend(),
        "dtype": "bfloat16",
        "n_rows": n,
        "n_features": d,
        "k": k,
        "f32_xla_iterations_per_sec": round(it_f32 / el_f32, 3),
        **_mfu_fields(2.0 * n * d * k * it_b16, el_b16, n_chips, peak),
    }


def _bench_logreg_bf16(jax, on_tpu, n_chips, peak):
    """LogisticRegression with config.dtype='bfloat16' at the headline
    shape of the CURRENT backend (4M x 256 on TPU, 200k x 64 on CPU) —
    on TPU the headline is already bf16 so this re-measures it at fewer
    iterations; on CPU it records the bf16-emulation counterpoint so
    f32 and bf16 lines both exist on every backend."""
    import time

    from dask_ml_tpu import config, datasets
    from dask_ml_tpu.linear_model import LogisticRegression

    n = 4_000_000 if on_tpu else 200_000
    n_feat = 256 if on_tpu else 64
    X, y = datasets.make_classification(
        n_samples=n, n_features=n_feat, random_state=0
    )
    max_iter = 20
    with config.set(dtype="bfloat16"):
        LogisticRegression(solver="lbfgs", max_iter=1, tol=0.0).fit(X, y)
        t0 = time.perf_counter()
        clf = LogisticRegression(solver="lbfgs", max_iter=max_iter,
                                 tol=0.0).fit(X, y)
        elapsed = time.perf_counter() - t0
    iters = clf.n_iter_ or max_iter
    return {
        "metric": "logreg_fit_samples_per_sec_per_chip_bf16",
        "value": round(n * iters / elapsed / n_chips, 1),
        "unit": "samples/s/chip",
        "backend": jax.default_backend(),
        "dtype": "bfloat16",
        "n_rows": n,
        "iters": int(iters),
    }


def _bench_rsvd(jax, on_tpu, n_chips, peak):
    """BASELINE configs[2]: tall-skinny randomized SVD completes."""
    import time

    import jax.numpy as jnp

    from dask_ml_tpu.decomposition import TruncatedSVD
    from dask_ml_tpu.parallel import as_sharded

    n = 1_000_000 if on_tpu else 100_000
    d = 512 if on_tpu else 128
    k = 32
    key = jax.random.PRNGKey(2)

    @jax.jit
    def gen():
        return jax.random.normal(key, (n, d), jnp.float32)

    X = as_sharded(jax.block_until_ready(gen()))
    q_iters = 4  # explicit so the flop model below matches what runs
    # cold run pays the (one-time, cached) XLA compile; the metric is the
    # warm completion — what a second call or a bigger same-shape matrix
    # experiences
    TruncatedSVD(n_components=k, algorithm="randomized", n_iter=q_iters,
                 random_state=0).fit(X)
    svd = TruncatedSVD(n_components=k, algorithm="randomized",
                       n_iter=q_iters, random_state=0)
    t0 = time.perf_counter()
    svd.fit(X)
    elapsed = time.perf_counter() - t0
    assert np.isfinite(svd.singular_values_).all()
    # Halko data passes: X@Omega + q power iters (X.T@Q, X@Qz each) +
    # Q.T@X, all (n, d)x(d, l) with l = k + 10 oversamples = 2ndl(2q+2)
    l = k + 10
    rsvd_flops = 2.0 * n * d * l * (2 * q_iters + 2)
    return {
        "metric": "randomized_svd_seconds",
        "value": round(elapsed, 3),
        "unit": "s",
        "backend": jax.default_backend(),
        "dtype": "float32",
        "n_rows": n,
        "n_features": d,
        "n_components": k,
        **_mfu_fields(rsvd_flops, elapsed, n_chips, peak),
    }


def _bench_incremental_sgd(jax, on_tpu, n_chips, peak):
    """BASELINE configs[3]: Incremental(SGDClassifier) streaming
    partial_fit over TPU-resident blocks — one full epoch, blocks gathered
    on device (take_rows), model state device-resident throughout."""
    import time

    import jax.numpy as jnp

    from dask_ml_tpu.models.sgd import SGDClassifier
    from dask_ml_tpu.parallel import as_sharded
    from dask_ml_tpu.wrappers import Incremental

    n = 2_000_000 if on_tpu else 400_000
    d = 128
    key = jax.random.PRNGKey(3)

    @jax.jit
    def gen():
        kx, ky = jax.random.split(key)
        X = jax.random.normal(kx, (n, d), jnp.float32)
        y = (X[:, 0] + 0.3 * jax.random.normal(ky, (n,)) > 0).astype(
            jnp.float32
        )
        return X, y

    X, y = jax.block_until_ready(gen())
    Xs, ys = as_sharded(X), as_sharded(y)
    inc = Incremental(SGDClassifier(max_iter=1, random_state=0),
                      shuffle_blocks=False)
    # two warmups: the first compiles at the fresh-zeros weight
    # sharding, the second at the steady-state replicated one
    inc.fit(Xs, ys)
    inc.fit(Xs, ys)
    t0 = time.perf_counter()
    inc.fit(Xs, ys)
    elapsed = time.perf_counter() - t0
    return {
        "metric": "incremental_sgd_samples_per_sec_per_chip",
        "value": round(n / elapsed / n_chips, 1),
        "unit": "samples/s/chip",
        "backend": jax.default_backend(),
        "dtype": "float32",
        "n_rows": n,
        "n_features": d,
        # one epoch: forward (2nd) + backward (2nd) over every sample
        **_mfu_fields(4.0 * n * d, elapsed, n_chips, peak),
    }


def _bench_streamed_sgd(jax, on_tpu, n_chips, peak):
    """Out-of-core SGD over a memmap through the instrumented
    BlockStream (VERDICT r4 weak #2): reports measured overlap — how
    much of each pass moved data (host slice + put + transfer wait) vs
    computed — and the block autotune's growth across epochs."""
    import os
    import tempfile
    import time

    import numpy as np

    from dask_ml_tpu import config
    from dask_ml_tpu.models.sgd import SGDClassifier

    n = 2_000_000 if on_tpu else 400_000
    d = 128
    epochs = 3
    # block height: n/32 as before on CPU; on TPU rounded DOWN to a
    # 128-multiple so the fused Pallas streamed kernels' grid
    # (ops/pallas_fused.stream_tile) engages instead of falling back
    block_rows = max(n // 32, 1)
    if on_tpu:
        block_rows = max(block_rows // 128 * 128, 128)
    rng = np.random.RandomState(7)
    path = os.path.join(tempfile.mkdtemp(), "bench_sgd_X.f32")
    X = np.memmap(path, dtype=np.float32, mode="w+", shape=(n, d))
    w = rng.randn(d).astype(np.float32)
    y = np.empty(n, np.float32)
    for lo in range(0, n, 200_000):
        hi = min(lo + 200_000, n)
        X[lo:hi] = rng.randn(hi - lo, d)
        y[lo:hi] = (X[lo:hi] @ w > 0)
    X.flush()
    Xr = np.memmap(path, dtype=np.float32, mode="r", shape=(n, d))
    # fix the block size so warmup compiles at EXACTLY the timed shape
    # (autotune stays off: a resize would recompile inside the timed
    # region and make the partition load-dependent)
    from dask_ml_tpu.utils.observability import (MetricsLogger,
                                                 active_logger)

    with config.set(stream_block_rows=block_rows,
                    stream_autotune=False):
        warm = SGDClassifier(max_iter=1, random_state=0, shuffle=False)
        warm.fit(Xr, y)  # one full epoch at the timed block shape
        clf = SGDClassifier(max_iter=epochs, random_state=0,
                            shuffle=False)
        # a bound logger turns on the readiness sync so wait_s (the
        # transfer-stall component of "moving") is actually measured,
        # and streams per-pass JSONL next to the memmap
        with MetricsLogger(path + ".stream.jsonl") as lg, \
                active_logger(lg):
            t0 = time.perf_counter()
            clf.fit(Xr, y)
            elapsed = time.perf_counter() - t0
    st = dict(getattr(clf, "_last_stream_stats", None) or {})
    if st.get("superblock_k"):
        # super-block passes stage + device_put on a background worker
        # (overlapped with the scan); the consumer's data-movement cost
        # is its measured STALL, not the worker's busy time
        moving = st.get("wait_s", 0)
    else:
        moving = st.get("host_s", 0) + st.get("put_s", 0) \
            + st.get("wait_s", 0)
    # the per-block path for the on-record super-block speedup ratio
    # (same data, same partition, one dispatch per block instead of
    # one per K)
    with config.set(stream_block_rows=block_rows,
                    stream_autotune=False, stream_superblock=False):
        pb_warm = SGDClassifier(max_iter=1, random_state=0, shuffle=False)
        pb_warm.fit(Xr, y)
        pb = SGDClassifier(max_iter=epochs, random_state=0, shuffle=False)
        t0 = time.perf_counter()
        pb.fit(Xr, y)
        pb_elapsed = time.perf_counter() - t0
    # bf16 streamed flavor (ISSUE 8): the same hot loop with the fit
    # compute dtype forced to bf16 — on TPU this is what the "auto"
    # policy serves by default (fused kernels at bf16 MXU rate); on CPU
    # it documents the software-bf16 penalty the auto policy's f32
    # fallback avoids. Recorded per backend, so the sentinel floor is
    # backend-matched.
    with config.set(stream_block_rows=block_rows, stream_autotune=False,
                    dtype="bfloat16"):
        b16_warm = SGDClassifier(max_iter=1, random_state=0,
                                 shuffle=False)
        b16_warm.fit(Xr, y)
        b16 = SGDClassifier(max_iter=epochs, random_state=0,
                            shuffle=False)
        t0 = time.perf_counter()
        b16.fit(Xr, y)
        b16_elapsed = time.perf_counter() - t0
    # demonstrate the opt-in autotune separately (not in the timed run):
    # 2 epochs, report where the block size and K land
    with config.set(stream_block_rows=block_rows,
                    stream_autotune=True):
        at = SGDClassifier(max_iter=2, random_state=0, shuffle=False)
        at.fit(Xr, y)
    at_st = dict(getattr(at, "_last_stream_stats", None) or {})
    os.unlink(path)
    bf16_metric = {
        "metric": "streamed_sgd_samples_per_sec_per_chip_bf16",
        "value": round(n * epochs / b16_elapsed / n_chips, 1),
        "unit": "samples/s/chip",
        "backend": jax.default_backend(),
        "dtype": "bfloat16",
        "fit_dtype": getattr(b16, "fit_dtype_", None),
        "n_rows": n,
        "n_features": d,
        "epochs": epochs,
        "ratio_vs_f32": round(elapsed / b16_elapsed, 3),
    }
    return [{
        "metric": "streamed_sgd_samples_per_sec_per_chip",
        "value": round(n * epochs / elapsed / n_chips, 1),
        "unit": "samples/s/chip",
        "backend": jax.default_backend(),
        "dtype": "float32",
        "n_rows": n,
        "n_features": d,
        "epochs": epochs,
        "overlap": {
            "block_rows": st.get("block_rows"),
            "n_blocks": st.get("n_blocks"),
            "last_pass_s": st.get("pass_s"),
            "moving_s": round(moving, 4),
            "compute_s": round(st.get("consume_s", 0.0), 4),
            "moving_frac": round(
                moving / max(st.get("pass_s", 0.0), 1e-9), 4
            ),
            # opt-in autotune's landing point after 2 epochs (untimed)
            "autotuned_block_rows": at_st.get("block_rows"),
            "autotuned_n_blocks": at_st.get("n_blocks"),
            "autotuned_superblock_k": at_st.get("superblock_k"),
        },
        "superblock": {
            # the fused hot loop's dispatch accounting (ISSUE 3): one
            # scan per K blocks, donated weight carry
            "superblock_k": st.get("superblock_k"),
            "dispatches_per_pass": st.get("dispatches_per_pass"),
            "per_block_samples_per_sec_per_chip": round(
                n * epochs / pb_elapsed / n_chips, 1
            ),
            "speedup_vs_per_block": round(pb_elapsed / elapsed, 3),
        },
        **_mfu_fields(4.0 * n * d * epochs, elapsed, n_chips, peak),
    }, bf16_metric]


def _bench_sharded_streaming(jax, on_tpu, n_chips):
    """Data-parallel superblock streaming (ISSUE 9): the streamed-SGD
    hot loop at data-axis widths {1, 8}. On CPU each width runs in its
    own grandchild process (`BENCH_SHARDED_CHILD`) so the virtual
    device count can differ per measurement; on TPU both widths run
    in-process over the real chips via config.stream_mesh. Records
    samples/s/chip per width plus the sharded flavor's AGGREGATE
    rows/s — on shared-silicon virtual devices the per-chip number
    documents plumbing overhead, on a real slice it is the scaling
    headline tpu_smoke round-9 verifies."""
    import subprocess
    import time

    def run_width(n_devices):
        if on_tpu:
            from dask_ml_tpu import config as _cfg
            from dask_ml_tpu.models.sgd import SGDClassifier

            import numpy as _np

            n, d, epochs = 400_000, 64, 2
            rng = _np.random.RandomState(9)
            X = rng.randn(n, d).astype(_np.float32)
            y = (X[:, 0] > 0).astype(_np.float32)
            sm = 1 if n_devices == 1 else 0
            with _cfg.set(stream_block_rows=n // 16,
                          stream_autotune=False, stream_mesh=sm):
                SGDClassifier(max_iter=1, random_state=0,
                              shuffle=False).fit(X, y)
                clf = SGDClassifier(max_iter=epochs, random_state=0,
                                    shuffle=False)
                t0 = time.perf_counter()
                clf.fit(X, y)
                elapsed = time.perf_counter() - t0
            st = dict(getattr(clf, "_last_stream_stats", None) or {})
            return {"n_devices": int(st.get("sb_shards", 1)),
                    "rows_per_sec": n * epochs / elapsed,
                    "n_rows": n, "epochs": epochs}
        # no XLA_FLAGS override: the grandchild's force_cpu_platform
        # APPENDS/RAISES the device-count flag inside whatever ambient
        # tuning flags exist — replacing the variable here would run
        # the sharded measurements under a different XLA configuration
        # than every other bench flavor
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            BENCH_SHARDED_CHILD=str(n_devices),
        )
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            timeout=180, capture_output=True, text=True,
        )
        out = _last_json_line(r.stdout)
        if out is None or out.get("error"):
            raise RuntimeError(
                f"sharded child (n_devices={n_devices}) failed: "
                f"{(out or {}).get('error')} "
                f"{(r.stderr or '')[-500:]}"
            )
        return out

    res = {nd: run_width(nd) for nd in (1, 8)}
    # metric names carry the ACTUAL data-parallel width, not the
    # requested one: on CPU the virtual-device forcing makes them equal
    # ({1, 8} per the recorded series), but a TPU attach runs stream_
    # mesh=0 at whatever the slice has — recording a 4-chip (or 1-chip)
    # run under a "dp8" name would seed sentinel floors for a series it
    # never measured
    entries = []
    seen = set()
    for nd in (1, 8):
        r = res[nd]
        chips = max(int(r["n_devices"]), 1)
        if chips in seen:
            continue  # 1-chip attach: the "sharded" run IS the dp1 run
        seen.add(chips)
        entries.append({
            "metric": f"streamed_sgd_sharded_dp{chips}"
                      f"_samples_per_sec_per_chip",
            "value": round(r["rows_per_sec"] / chips, 1),
            "unit": "samples/s/chip",
            "backend": jax.default_backend(),
            "n_devices": chips,
            "n_rows": r["n_rows"],
            "epochs": r["epochs"],
        })
    width = max(int(res[8]["n_devices"]), 1)
    if width > 1:
        entries.append({
            "metric": f"streamed_sgd_sharded_dp{width}_rows_per_sec",
            "value": round(res[8]["rows_per_sec"], 1),
            "unit": "rows/s",
            "backend": jax.default_backend(),
            "n_devices": width,
            # the honest shared-silicon caveat: virtual CPU devices
            # split the same cores, so aggregate ~flat is expected
            # off-TPU
            "vs_dp1_ratio": round(
                res[8]["rows_per_sec"]
                / max(res[1]["rows_per_sec"], 1e-9), 3,
            ),
        })
    return entries


def _sharded_child_main():
    """Grandchild body for `_bench_sharded_streaming` /
    `_bench_fused_sharded_stream` on CPU: one streamed-SGD fit at the
    ambient (forced) virtual device count — with ``BENCH_SHARDED_FUSED``
    set, the fused Pallas bodies run inside the shard_map programs
    through the interpreter at 128-multiple per-shard slabs — one JSON
    line out."""
    out = {"error": None}
    try:
        from dask_ml_tpu._platform import force_cpu_platform

        n_devices = int(os.environ["BENCH_SHARDED_CHILD"])
        fused = bool(os.environ.get("BENCH_SHARDED_FUSED"))
        force_cpu_platform(n_devices=n_devices)
        import numpy as np

        from dask_ml_tpu import config as _cfg
        from dask_ml_tpu.models.sgd import SGDClassifier

        n, d, epochs = 200_000, 32, 2
        if fused:
            # interpreter-speed kernels: a smaller honest measurement,
            # at a block height whose per-shard slab is a 128-multiple
            # (the fused tile gate)
            n, block_rows = 65_536, 2048
        else:
            block_rows = n // 16
        rng = np.random.RandomState(9)
        X = rng.randn(n, d).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        sm = 1 if n_devices == 1 else 0
        with _cfg.set(stream_block_rows=block_rows,
                      stream_autotune=False, stream_mesh=sm,
                      pallas_stream_interpret=fused):
            SGDClassifier(max_iter=1, random_state=0,
                          shuffle=False).fit(X, y)  # warm compiles
            clf = SGDClassifier(max_iter=epochs, random_state=0,
                                shuffle=False)
            t0 = time.perf_counter()
            clf.fit(X, y)
            elapsed = time.perf_counter() - t0
        st = dict(getattr(clf, "_last_stream_stats", None) or {})
        want = n_devices
        if int(st.get("sb_shards", 1)) != want:
            raise RuntimeError(
                f"sharded child ran at sb_shards={st.get('sb_shards')}"
                f", wanted {want}"
            )
        info = dict(getattr(clf, "solver_info_", None) or {})
        if fused and not info.get("fused_stream"):
            raise RuntimeError(
                "fused child fell back to the XLA bodies "
                f"(reason={info.get('fused_stream_reason')})"
            )
        out.update(
            metric="streamed_sgd_sharded_child",
            n_devices=int(st.get("sb_shards", 1)),
            rows_per_sec=n * epochs / elapsed,
            n_rows=n, epochs=epochs,
            dispatches_per_pass=st.get("dispatches_per_pass"),
            fused=fused,
        )
    except Exception as exc:  # one JSON line no matter what
        out["error"] = f"{type(exc).__name__}: {exc}"
        out["metric"] = "streamed_sgd_sharded_child"
    print(json.dumps(out), flush=True)


def _mesh2d_measure(shape):
    """One feature-sharded measurement (ISSUE 18), shared by the TPU
    in-process path and the CPU grandchild: assert the 1-D stage
    REFUSES the wide-d fit under the simulated per-device byte budget
    (typed StreamBudgetExceeded), then time the same fit — and a
    streamed randomized PCA — on the 2-D ``shape`` mesh, where the X
    slabs stage as (rows/D, d/M) per-device tiles under the SAME
    budget."""
    import time

    import numpy as np

    from dask_ml_tpu import config as _cfg
    from dask_ml_tpu import observability as obs
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.models.pca import PCA
    from dask_ml_tpu.parallel.streaming import (BlockStream,
                                                StreamBudgetExceeded)

    n, d, block_rows = 65_536, 512, 2048
    # single-device staging needs K x 2048 x 512 x 4 = ~33.5MB; the 2x4
    # tiles need ~4.3MB — the budget sits between, so the SAME fit is a
    # typed refusal on 1-D and a measurement on the hybrid mesh
    budget = 8_000_000
    rng = np.random.RandomState(18)
    X = rng.randn(n, d).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)

    refused = False
    try:
        with _cfg.set(stream_block_rows=block_rows,
                      stream_autotune=False, stream_mesh=1,
                      stream_device_byte_budget=budget):
            LogisticRegression(solver="lbfgs", max_iter=2).fit(X, y)
    except StreamBudgetExceeded:
        refused = True
    if not refused:
        raise RuntimeError(
            "1-D stage did not refuse the wide-d fit under "
            f"stream_device_byte_budget={budget}"
        )

    with _cfg.set(stream_block_rows=block_rows, stream_autotune=False,
                  stream_mesh=0, mesh_shape=shape,
                  stream_device_byte_budget=budget):
        st = BlockStream((X, y.astype(np.float32)),
                         block_rows=block_rows)
        D, M = st.sb_data_shards(), st.sb_model_shards()
        if M <= 1:
            raise RuntimeError(
                "model axis did not engage "
                f"(reason={st.model_tile_reason})"
            )
        LogisticRegression(solver="lbfgs", max_iter=2).fit(X, y)  # warm
        obs.counters_reset()
        t0 = time.perf_counter()
        LogisticRegression(solver="lbfgs", max_iter=8).fit(X, y)
        glm_s = time.perf_counter() - t0
        # rows actually streamed through the superblock plane (lbfgs
        # pass count is line-search dependent; the counter is exact)
        glm_rows = obs.counters_snapshot().get(
            "superblock_blocks", 0) * block_rows
        if glm_rows <= 0:
            raise RuntimeError("feature-sharded GLM fit did not stream")

        PCA(n_components=8, svd_solver="randomized",
            random_state=0).fit(X)                      # warm compiles
        t0 = time.perf_counter()
        PCA(n_components=8, svd_solver="randomized",
            random_state=0).fit(X)
        pca_s = time.perf_counter() - t0
    return {
        "mesh": f"{D}x{M}", "n_rows": n, "d": d,
        "glm_rows_per_sec": glm_rows / glm_s,
        # the streamed rSVD pass plan is FIXED: 1 moments + 3 range
        "pca_rows_per_sec": 4 * n / pca_s,
    }


def _mesh2d_child_main():
    """Grandchild body for `_bench_feature_sharded` on CPU: the whole
    measurement at a forced 8-virtual-device pool (mesh 2x4). One JSON
    line out."""
    out = {"error": None, "metric": "feature_sharded_child"}
    try:
        from dask_ml_tpu._platform import force_cpu_platform

        force_cpu_platform(
            n_devices=int(os.environ["BENCH_MESH2D_CHILD"])
        )
        out.update(_mesh2d_measure("2x4"))
    except Exception as exc:  # one JSON line no matter what
        out["error"] = f"{type(exc).__name__}: {exc}"
    print(json.dumps(out), flush=True)


def _bench_feature_sharded(jax, on_tpu, n_chips):
    """Feature-sharded streaming (ISSUE 18): a (rows, d) GLM fit the
    1-D path REFUSES under the simulated per-device byte budget
    (typed StreamBudgetExceeded) completes — and is timed — on the 2-D
    hybrid mesh, plus the streamed randomized PCA at the same width.
    On CPU the measurement runs in a grandchild so the 8-virtual-device
    pool can't leak into other sections; on TPU it runs in-process over
    the real chips with an inferred "-1x2" model axis."""
    if on_tpu:
        if n_chips < 2 or n_chips % 2:
            raise RuntimeError(
                f"needs an even multi-chip attach for a model axis, "
                f"have {n_chips}"
            )
        res = _mesh2d_measure("-1x2")
    else:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   BENCH_MESH2D_CHILD="8")
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            timeout=300, capture_output=True, text=True,
        )
        res = _last_json_line(r.stdout)
        if res is None or res.get("error"):
            raise RuntimeError(
                f"mesh2d child failed: {(res or {}).get('error')} "
                f"{(r.stderr or '')[-500:]}"
            )
    backend = jax.default_backend()
    common = {"backend": backend, "mesh": res["mesh"],
              "n_rows": res["n_rows"], "d": res["d"],
              "refused_1d": True}
    return [
        dict(common, metric="glm_feature_sharded_rows_per_sec",
             value=round(res["glm_rows_per_sec"], 1), unit="rows/s"),
        dict(common, metric="pca_streamed_rows_per_sec",
             value=round(res["pca_rows_per_sec"], 1), unit="rows/s"),
    ]


def _bench_fused_sharded_stream(jax, on_tpu, n_chips):
    """Fused x sharded streamed SGD (ISSUE 12) + the grad-accum flavor.

    On TPU the fused Pallas bodies run COMPILED inside the shard_map
    scan programs over the real chips; on CPU they run through the
    Pallas INTERPRETER in an 8-virtual-device grandchild — recorded
    honestly (backend "cpu", pallas_mode "interpret"), the same way the
    dp8 series documents virtual-device plumbing rather than real
    scaling. The grad-accum metric times the A=2 flavor in-process:
    its per-update host merge is the price of the cross-host-capable
    optimizer, and the recorded ratio vs the sequential flavor keeps
    that price visible."""
    import subprocess
    import time

    entries = []
    if on_tpu:
        from dask_ml_tpu import config as _cfg
        from dask_ml_tpu.models.sgd import SGDClassifier as _SGD

        import numpy as _np

        n, d, epochs = 400_000, 64, 2
        rng = _np.random.RandomState(12)
        X = rng.randn(n, d).astype(_np.float32)
        y = (X[:, 0] > 0).astype(_np.float32)
        with _cfg.set(stream_block_rows=2048, stream_autotune=False,
                      stream_mesh=0):
            _SGD(max_iter=1, random_state=0, shuffle=False).fit(X, y)
            clf = _SGD(max_iter=epochs, random_state=0, shuffle=False)
            t0 = time.perf_counter()
            clf.fit(X, y)
            elapsed = time.perf_counter() - t0
        st = dict(getattr(clf, "_last_stream_stats", None) or {})
        info = dict(getattr(clf, "solver_info_", None) or {})
        if not info.get("fused_stream"):
            # same contract as the CPU child: never record an unfused
            # run under the fused metric name (it would seed a
            # sentinel floor for a series that never ran — e.g. a
            # slice width whose per-shard slabs miss the 128-multiple)
            raise RuntimeError(
                "fused sharded fit fell back to the XLA bodies "
                f"(reason={info.get('fused_stream_reason')})"
            )
        chips = max(int(st.get("sb_shards", 1)), 1)
        entries.append({
            "metric": f"streamed_sgd_sharded_fused_dp{chips}"
                      f"_samples_per_sec_per_chip",
            "value": round(n * epochs / elapsed / chips, 1),
            "unit": "samples/s/chip",
            "backend": jax.default_backend(),
            "pallas_mode": "compiled",
            "fused_stream": True,
            "n_devices": chips, "n_rows": n, "epochs": epochs,
        })
    else:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   BENCH_SHARDED_CHILD="8", BENCH_SHARDED_FUSED="1")
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            timeout=600, capture_output=True, text=True,
        )
        out = _last_json_line(r.stdout)
        if out is None or out.get("error"):
            raise RuntimeError(
                f"fused sharded child failed: "
                f"{(out or {}).get('error')} {(r.stderr or '')[-500:]}"
            )
        chips = max(int(out["n_devices"]), 1)
        entries.append({
            "metric": f"streamed_sgd_sharded_fused_dp{chips}"
                      f"_samples_per_sec_per_chip",
            "value": round(out["rows_per_sec"] / chips, 1),
            "unit": "samples/s/chip",
            "backend": jax.default_backend(),
            # honest recording: this box runs the kernels through the
            # Pallas interpreter on shared-silicon virtual devices —
            # the number gates plumbing regressions, not chip speed
            "pallas_mode": "interpret",
            "n_devices": chips,
            "n_rows": out["n_rows"], "epochs": out["epochs"],
        })

    # grad-accum flavor (in-process; the sequential comparison uses the
    # same data/partition)
    from dask_ml_tpu import config as _cfg
    from dask_ml_tpu.models.sgd import SGDClassifier as _SGD

    import numpy as _np

    n, d, epochs, A = 200_000, 32, 2, 2
    rng = _np.random.RandomState(13)
    X = rng.randn(n, d).astype(_np.float32)
    y = (X[:, 0] > 0).astype(_np.float32)
    base = dict(stream_block_rows=n // 16, stream_autotune=False)

    def timed(**kw):
        with _cfg.set(**base, **kw):
            _SGD(max_iter=1, random_state=0, shuffle=False).fit(X, y)
            clf = _SGD(max_iter=epochs, random_state=0, shuffle=False)
            t0 = time.perf_counter()
            clf.fit(X, y)
            return clf, time.perf_counter() - t0

    seq, t_seq = timed()
    ga, t_ga = timed(stream_grad_accum=A)
    entries.append({
        "metric": f"streamed_sgd_grad_accum_a{A}_samples_per_sec_per_chip",
        "value": round(n * epochs / t_ga / n_chips, 1),
        "unit": "samples/s/chip",
        "backend": jax.default_backend(),
        "grad_accum": A,
        "n_rows": n, "epochs": epochs,
        # the documented price of the cross-host-capable flavor: one
        # host merge + separate apply dispatch per update
        "ratio_vs_sequential": round(t_seq / t_ga, 3),
    })
    return entries


def _bench_sparse_stream(jax, on_tpu, n_chips):
    """Device-resident sparse streaming (ISSUE 13) at the hashed-text
    shape: streamed SGD and GLM over a density ~1%, d=2**14 CSR corpus
    — the bucketed-nnz scan (config.stream_sparse) vs the per-block
    densify baseline (today's default) on the SAME data and block
    partition. The acceptance bar is >= 2x rows/s for at least one of
    SGD/GLM on CPU; nnz/s is the honest cost axis (the sparse path's
    work is nnz-proportional, the baseline's is n*d)."""
    import time

    import numpy as np
    import scipy.sparse as sp

    from dask_ml_tpu import config
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.models.sgd import SGDClassifier

    n = 120_000 if on_tpu else 60_000
    d = 2 ** 14
    npr = max(d // 100, 1)               # density ~1%
    epochs = 2
    block_rows = 1024
    rng = np.random.RandomState(11)
    # fixed-nnz-per-row CSR built directly — sp.random at this n*d is
    # pathological; duplicate column hits sum on both paths identically
    indices = rng.randint(0, d, size=n * npr).astype(np.int32)
    data = rng.rand(n * npr).astype(np.float32)
    indptr = np.arange(0, n * npr + 1, npr, dtype=np.int64)
    X = sp.csr_matrix((data, indices, indptr), shape=(n, d))
    w = rng.randn(d).astype(np.float32)
    eta = X @ w
    y = (eta > np.median(eta)).astype(np.float64)
    nnz = int(X.nnz)

    def timed_sgd(sparse_on):
        with config.set(stream_block_rows=block_rows,
                        stream_autotune=False, stream_mesh=1,
                        stream_sparse=sparse_on):
            warm = SGDClassifier(max_iter=1, random_state=0,
                                 shuffle=False)
            warm.fit(X, y)
            clf = SGDClassifier(max_iter=epochs, random_state=0,
                                shuffle=False)
            t0 = time.perf_counter()
            clf.fit(X, y)
            return time.perf_counter() - t0, clf

    def timed_glm(sparse_on):
        with config.set(stream_block_rows=block_rows,
                        stream_autotune=False, stream_mesh=1,
                        stream_sparse=sparse_on):
            warm = LogisticRegression(solver="gradient_descent",
                                      max_iter=1)
            warm.fit(X, y)
            clf = LogisticRegression(solver="gradient_descent",
                                     max_iter=3)
            t0 = time.perf_counter()
            clf.fit(X, y)
            return time.perf_counter() - t0, clf

    sp_s, sp_clf = timed_sgd(True)
    if not (sp_clf.solver_info_ or {}).get("sparse_stream"):
        raise RuntimeError(
            "sparse SGD bench fell back to densify (reason="
            f"{(sp_clf.solver_info_ or {}).get('sparse_stream_reason')})"
            " — a densify run must never seed a sparse-named floor"
        )
    dn_s, _ = timed_sgd(False)
    g_sp_s, g_clf = timed_glm(True)
    if not (g_clf.solver_info_ or {}).get("sparse_stream"):
        raise RuntimeError(
            "sparse GLM bench fell back to densify (reason="
            f"{(g_clf.solver_info_ or {}).get('sparse_stream_reason')})"
        )
    g_dn_s, g_ref = timed_glm(False)
    # each run normalizes by its OWN pass count: line-search trials
    # branch on float values, so the two flavors may take different
    # numbers of data passes for the same max_iter — the speedup is a
    # per-pass (rows/s vs rows/s) comparison, never raw wall clock of
    # unequal work
    g_passes = max(int((g_clf.solver_info_ or {}).get("data_passes", 1)),
                   1)
    g_dn_passes = max(
        int((g_ref.solver_info_ or {}).get("data_passes", 1)), 1
    )
    g_sp_rps = n * g_passes / g_sp_s
    g_dn_rps = n * g_dn_passes / g_dn_s
    backend = jax.default_backend()
    return [
        {
            "metric": "streamed_sparse_sgd_rows_per_sec",
            "value": round(n * epochs / sp_s, 1),
            "unit": "rows/s",
            "backend": backend,
            "dtype": "float32",
            "n_rows": n, "n_features": d, "density": npr / d,
            "epochs": epochs, "block_rows": block_rows,
            "nnz_per_sec": round(nnz * epochs / sp_s, 1),
            "densify_rows_per_sec": round(n * epochs / dn_s, 1),
            "speedup_vs_densify": round(dn_s / sp_s, 3),
            "criterion": ">=2x vs per-block densify",
        },
        {
            "metric": "streamed_sparse_glm_rows_per_sec",
            "value": round(g_sp_rps, 1),
            "unit": "rows/s",
            "backend": backend,
            "dtype": "float32",
            "n_rows": n, "n_features": d, "density": npr / d,
            "data_passes": g_passes, "block_rows": block_rows,
            "densify_data_passes": g_dn_passes,
            "nnz_per_sec": round(nnz * g_passes / g_sp_s, 1),
            "densify_rows_per_sec": round(g_dn_rps, 1),
            "speedup_vs_densify": round(g_sp_rps / g_dn_rps, 3),
        },
    ]


def _bench_int8_serving(jax, on_tpu, n_chips):
    """Int8 weight-quantized serving flavor (ISSUE 8): warm f32 and
    int8 compiled predict entry points for the same fitted logreg, run
    interleaved best-of passes over a ladder-bucket batch, report int8
    rows/s + the ratio vs f32 + prediction agreement (the >=99.5%
    criterion the parity suite enforces)."""
    import time

    import numpy as np

    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.wrappers import compiled_batch_fn

    n, d = (400_000 if on_tpu else 100_000), 64
    rng = np.random.RandomState(9)
    X = rng.randn(n, d).astype(np.float32)
    w = rng.randn(d).astype(np.float32)
    y = (X @ w + 0.5 * rng.randn(n) > 0).astype(np.float32)
    clf = LogisticRegression(solver="lbfgs", max_iter=30).fit(
        X[:50_000], y[:50_000]
    )
    f32 = compiled_batch_fn(clf, "predict")
    q8 = compiled_batch_fn(clf, "predict", quantize="int8")
    batch = X[:4096]
    import jax as _jax

    _jax.block_until_ready(f32._fn(f32._state[0], batch))   # warm
    _jax.block_until_ready(q8._fn(q8._state[0], batch))
    reps = 30

    def best_of(fn):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn(batch)
            np.asarray(out)
            best = min(best, time.perf_counter() - t0)
        return len(batch) * reps / best

    r32 = best_of(f32)
    r8 = best_of(q8)
    agree = float(np.mean(f32(X[:100_000]) == q8(X[:100_000])))
    return {
        "metric": "serving_predict_int8_rows_per_sec_per_chip",
        "value": round(r8 / n_chips, 1),
        "unit": "rows/s/chip",
        "backend": jax.default_backend(),
        "dtype": "int8xbf16",
        "n_features": d,
        "batch_rows": int(len(batch)),
        "f32_rows_per_sec_per_chip": round(r32 / n_chips, 1),
        "ratio_vs_f32": round(r8 / r32, 3),
        "prediction_agreement": round(agree, 5),
    }


def _bench_hyperband(jax, on_tpu, n_chips):
    """BASELINE configs[4]: HyperbandSearchCV wall clock. Since ISSUE
    14 the search cohort rides the streamed superblock plane (one
    BlockStream pass per adaptive round, slot-rung scans); the section
    times BOTH planes over the SAME host data and block partition —
    ``hyperband_seconds`` records the default (streamed) path,
    ``hyperband_device_plane_seconds`` the ``search_stream=False``
    device-resident cohort machinery it replaced, and the ratio is the
    honest A/B on identical bracket schedules (scores asserted equal).
    On this repo's 2-core CPU box the ratio is recorded as measured
    (~1.4-1.7x steady state — the streamed plane removes the device
    plane's per-round as_sharded+stack copies but shares its XLA step
    kernels); the >=2x regime is real TPU, where the fused cohort
    kernels engage and the removed copies are genuine HBM DMA —
    asserted by tpu_smoke round-13, like every other on-chip claim.
    ``hyperband_rows_per_sec`` + ``n_candidates`` land in the metrics
    so bench_sentinel can seed floors for the search plane."""
    import time

    from dask_ml_tpu import config
    from dask_ml_tpu.model_selection import HyperbandSearchCV
    from dask_ml_tpu.models.sgd import SGDClassifier

    n = 400_000
    d = 128
    rng = np.random.RandomState(4)
    X = rng.randn(n, d).astype(np.float32)
    y = (X[:, 0] + 0.5 * rng.randn(n) > 0).astype(np.float32)
    params = {"alpha": [1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 1e-2],
              "eta0": [0.01, 0.03, 0.05, 0.1, 0.3, 0.5]}

    def run_search(streamed):
        with config.set(search_stream=streamed):
            search = HyperbandSearchCV(
                SGDClassifier(tol=1e-3, random_state=0), params,
                max_iter=27, aggressiveness=3, random_state=0,
            )
            search.fit(X, y, classes=[0.0, 1.0])
        return search

    def timed(streamed):
        run_search(streamed)  # compile warmup: the metric is warm
        t0 = time.perf_counter()
        search = run_search(streamed)
        return search, time.perf_counter() - t0

    search, elapsed = timed(True)
    dev_search, dev_elapsed = timed(False)
    assert search.best_params_ == dev_search.best_params_ and \
        abs(search.best_score_ - dev_search.best_score_) <= 1e-6, (
        "streamed vs device-plane Hyperband diverged — the ratio "
        "below would compare different searches"
    )
    n_trials = len(search.cv_results_["params"])
    total_pf = int(np.sum(search.cv_results_["partial_fit_calls"]))
    meta = search.metadata_["stream"]
    # a fallback run must never seed streamed-named floors (same rule
    # as the sparse section): fail the section loudly instead
    assert meta.get("streamed"), (
        "hyperband bench did not engage the streamed cohort plane "
        f"(metadata: {meta}) — refusing to record streamed metrics "
        "from a device-plane run"
    )
    # rows the bracket actually touched: every partial_fit call trains
    # one block of the shared stream partition
    rows_touched = total_pf * meta["block_rows"]
    backend = jax.default_backend()
    head = {
        "metric": "hyperband_seconds",
        "value": round(elapsed, 3),
        "unit": "s",
        "backend": backend,
        "dtype": "float32",
        "n_rows": n,
        "n_features": d,
        "n_trials": n_trials,
        "n_candidates": n_trials,
        "partial_fit_calls": total_pf,
        "best_score": round(float(search.best_score_), 4),
        "stream_plane": {k: meta[k] for k in
                         ("n_blocks", "block_rows", "n_slots",
                          "dispatches", "shards", "sparse", "fused")},
        "device_plane_seconds": round(dev_elapsed, 3),
        "vs_device_plane": round(dev_elapsed / elapsed, 3),
    }
    rate = {
        "metric": "hyperband_rows_per_sec",
        "value": round(rows_touched / elapsed, 1),
        "unit": "rows/s",
        "backend": backend,
        "dtype": "float32",
        "n_candidates": n_trials,
        "rows_touched": int(rows_touched),
    }
    dev = {
        "metric": "hyperband_device_plane_seconds",
        "value": round(dev_elapsed, 3),
        "unit": "s",
        "backend": backend,
        "dtype": "float32",
        "n_candidates": n_trials,
    }
    return [head, rate, dev]


def _bench_serving(jax, on_tpu, n_chips):
    """Serving section: batched ModelServer throughput + p50/p99 latency
    over concurrent ragged requests vs the naive one-request-at-a-time
    predict loop on the SAME fitted model (which pays a fresh XLA
    compile per novel request shape plus a host->device hop per call —
    exactly what the bucket-ladder micro-batcher amortizes away)."""
    import threading as _threading
    import time

    import jax.numpy as jnp

    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.parallel import as_sharded
    from dask_ml_tpu.serving import BucketLadder, ModelServer

    n = 200_000 if on_tpu else 20_000
    d = 128 if on_tpu else 32
    key = jax.random.PRNGKey(7)

    @jax.jit
    def gen():
        kx, ky = jax.random.split(key)
        X = jax.random.normal(kx, (n, d), jnp.float32)
        y = (X[:, 0] + 0.3 * jax.random.normal(ky, (n,)) > 0).astype(
            jnp.float32
        )
        return X, y

    X, y = jax.block_until_ready(gen())
    clf = LogisticRegression(solver="lbfgs", max_iter=20).fit(
        as_sharded(X), as_sharded(y)
    )
    Xh = np.asarray(X)

    # ragged request mix: sizes drawn log-uniform in [1, 256]
    rng = np.random.RandomState(11)
    n_requests = 400
    sizes = np.maximum(np.exp(
        rng.uniform(0, np.log(256), size=n_requests)
    ).astype(int), 1)
    offs = [int(rng.randint(0, n - s)) for s in sizes]
    requests = [Xh[i:i + int(s)] for s, i in zip(sizes, offs)]
    total_rows = int(sizes.sum())

    # naive loop: per-request direct predict (compiles per novel padded
    # shape; measured over the SAME mix). One untimed pass would hide
    # the compile cost the serving path exists to remove, so the naive
    # number includes it — that asymmetry is the product claim, and the
    # steady-state comparison is still dominated by per-call dispatch.
    t0 = time.perf_counter()
    for r in requests:
        clf.predict(r)
    naive_s = time.perf_counter() - t0

    srv = ModelServer(
        clf, methods=("predict",), ladder=BucketLadder(8, 512, 2.0),
        batch_window_ms=1.0, timeout_ms=0,
    ).warmup()
    n_clients = 8
    shares = [requests[c::n_clients] for c in range(n_clients)]
    with srv:
        t0 = time.perf_counter()

        def client(c):
            for r in shares[c]:
                srv.predict(r)

        threads = [_threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        served_s = time.perf_counter() - t0
        stats = srv.stats()
    lat = stats["latency_s"]
    return {
        "metric": "serving_throughput_rows_per_sec",
        "value": round(total_rows / served_s, 1),
        "unit": "rows/s",
        "vs_baseline": round(naive_s / served_s, 3),
        "backend": jax.default_backend(),
        "dtype": "float32",
        "n_requests": n_requests,
        "total_rows": total_rows,
        "n_clients": n_clients,
        "batches": stats["batches"],
        "latency_p50_ms": round(lat["p50"] * 1e3, 3),
        "latency_p99_ms": round(lat["p99"] * 1e3, 3),
        "baseline": {
            "what": "naive per-request clf.predict loop, same request "
                    "mix (pays per-shape compiles + per-call dispatch)",
            "seconds": round(naive_s, 3),
            "rows_per_sec": round(total_rows / naive_s, 1),
        },
        "served_seconds": round(served_s, 3),
    }


def _bench_drift(jax, on_tpu, n_chips):
    """Drift-overhead section (ISSUE 7): the quality plane must be
    near-free. Two numbers:

    - sketch fold throughput — rows/s through ``FeatureSketch.fold``
      at serving width (the per-batch host cost the serving worker
      pays);
    - serving overhead — the SAME warmed closed-loop ragged mix served
      with ``obs_drift`` on vs off; criterion: the ratio stays >= 0.97
      (<= 3% throughput regression with sketches + shadow sampling on).
    """
    import threading as _threading
    import time

    from dask_ml_tpu.observability import FeatureSketch, drift
    from dask_ml_tpu.datasets import make_classification
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.serving import BucketLadder, ModelServer

    d = 32
    n = 20_000
    X, y = make_classification(n_samples=n, n_features=d,
                               n_informative=d // 4, random_state=0)
    clf = LogisticRegression(solver="lbfgs", max_iter=20).fit(X, y)
    Xh = X.to_numpy().astype(np.float32)

    # -- sketch fold cost per 10k rows ------------------------------------
    sk = FeatureSketch(d)
    block = Xh[:10_000]
    sk.fold(block)                        # warm allocation
    reps = 20
    t0 = time.perf_counter()
    for _ in range(reps):
        sk.fold(block)
    fold_s = (time.perf_counter() - t0) / reps
    fold_rows_per_sec = block.shape[0] / fold_s

    # -- serving throughput: sketches on vs off ---------------------------
    rng = np.random.RandomState(11)
    n_requests = 400
    sizes = np.maximum(np.exp(
        rng.uniform(0, np.log(256), size=n_requests)
    ).astype(int), 1)
    offs = [int(rng.randint(0, n - s)) for s in sizes]
    requests = [Xh[i:i + int(s)] for s, i in zip(sizes, offs)]
    total_rows = int(sizes.sum())
    n_clients = 8
    shares = [requests[c::n_clients] for c in range(n_clients)]

    def drive(srv):
        def client(c):
            for r in shares[c]:
                srv.predict(r)

        threads = [_threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    def build(obs_drift_on):
        from dask_ml_tpu import config

        # monitor cadence off: the overhead under test is the fold on
        # the serving path, not a background compute tick landing
        # mid-pass and adding variance
        with config.set(obs_drift=obs_drift_on,
                        obs_drift_interval_s=0.0):
            return ModelServer(
                clf, methods=("predict",),
                ladder=BucketLadder(8, 512, 2.0),
                batch_window_ms=1.0, timeout_ms=0,
            ).warmup()

    # INTERLEAVED passes over two live servers: shared-box load drifts
    # on the same timescale as a pass, so back-to-back blocks of
    # off-then-on confound the machine with the knob — alternating
    # passes and taking each mode's best cancels it
    srv_off, srv_on = build(False), build(True)
    t_offs, t_ons = [], []
    with srv_off, srv_on:
        drive(srv_off)                     # warm passes
        drive(srv_on)
        for _ in range(4):
            t_offs.append(drive(srv_off))
            t_ons.append(drive(srv_on))
    off_s, on_s = min(t_offs), min(t_ons)
    drift.reset()                          # bench must not leak sketches
    ratio = off_s / on_s                   # >= 1.0 means no overhead
    entries = [
        {
            "metric": "drift_sketch_fold_rows_per_sec",
            "value": round(fold_rows_per_sec, 1),
            "unit": "rows/s",
            "backend": jax.default_backend(),
            "dtype": "float32",
            "n_features": d,
            "fold_seconds_per_10k_rows": round(fold_s, 6),
        },
        {
            "metric": "drift_serving_overhead_ratio",
            "value": round(ratio, 4),
            "unit": "ratio",
            "backend": jax.default_backend(),
            "dtype": "float32",
            "criterion": ">= 0.97 (sketches cost <= 3% throughput)",
            "criterion_met": bool(ratio >= 0.97),
            "n_requests": n_requests,
            "total_rows": total_rows,
            "rows_per_sec_off": round(total_rows / off_s, 1),
            "rows_per_sec_on": round(total_rows / on_s, 1),
        },
    ]
    from dask_ml_tpu.observability import MetricsLogger

    metrics_file = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_metrics.jsonl"
    )
    with MetricsLogger(metrics_file) as _lg:
        for e in entries:
            _lg.log(kind="bench_drift", **e)
    return entries


def _bench_request_trace(jax, on_tpu, n_chips):
    """Request-trace overhead section (ISSUE 16): the trace plane's
    cost, measured. The SAME warmed closed-loop ragged mix served with
    ``obs_trace_sample=0`` (the default — no trace object ever
    allocated, the zero-overhead contract the jaxpr-identity test
    pins) vs ``1.0`` (every request stage-stamped, tail-sampled,
    histogram-folded). Tracing is host-side Python (~20us per request
    after the cadence fix in ``_slow_threshold``); against ms-scale
    accelerator steps that amortizes below 3% (criterion >= 0.97 on
    TPU), but this CPU bench's sub-ms batches are an adversarial
    denominator — there the criterion is >= 0.70 and the floor
    sentinel guards the recorded ratio against regression."""
    import threading as _threading
    import time

    from dask_ml_tpu.datasets import make_classification
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.observability import traces_reset
    from dask_ml_tpu.serving import BucketLadder, ModelServer

    d = 32
    n = 20_000
    X, y = make_classification(n_samples=n, n_features=d,
                               n_informative=d // 4, random_state=0)
    clf = LogisticRegression(solver="lbfgs", max_iter=20).fit(X, y)
    Xh = X.to_numpy().astype(np.float32)

    rng = np.random.RandomState(13)
    n_requests = 400
    sizes = np.maximum(np.exp(
        rng.uniform(0, np.log(256), size=n_requests)
    ).astype(int), 1)
    offs = [int(rng.randint(0, n - s)) for s in sizes]
    requests = [Xh[i:i + int(s)] for s, i in zip(sizes, offs)]
    total_rows = int(sizes.sum())
    n_clients = 8
    shares = [requests[c::n_clients] for c in range(n_clients)]

    def drive(srv):
        def client(c):
            for r in shares[c]:
                srv.predict(r)

        threads = [_threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    def build(sample):
        from dask_ml_tpu import config

        # a small keep bound: the steady-state cost under test is the
        # stamps + sampler decision + histogram folds, not an unbounded
        # retention deque
        with config.set(obs_trace_sample=sample, obs_trace_keep=64,
                        obs_drift=False):
            return ModelServer(
                clf, methods=("predict",),
                ladder=BucketLadder(8, 512, 2.0),
                batch_window_ms=1.0, timeout_ms=0,
            ).warmup()

    # interleaved passes, each mode's best — same confound control as
    # the drift section (shared-box load drifts on pass timescales)
    srv_off, srv_on = build(0.0), build(1.0)
    t_offs, t_ons = [], []
    with srv_off, srv_on:
        drive(srv_off)                     # warm passes
        drive(srv_on)
        for _ in range(4):
            t_offs.append(drive(srv_off))
            t_ons.append(drive(srv_on))
    off_s, on_s = min(t_offs), min(t_ons)
    traces_reset()                         # bench must not leak sampler state
    ratio = off_s / on_s                   # >= 1.0 means no overhead
    thresh = 0.97 if on_tpu else 0.70
    entry = {
        "metric": "request_trace_overhead_ratio",
        "value": round(ratio, 4),
        "unit": "ratio",
        "backend": jax.default_backend(),
        "dtype": "float32",
        "criterion": f">= {thresh} (host-side tracing vs this backend's "
                     "step time; <= 3% on accelerator-scale steps)",
        "criterion_met": bool(ratio >= thresh),
        "n_requests": n_requests,
        "total_rows": total_rows,
        "rows_per_sec_untraced": round(total_rows / off_s, 1),
        "rows_per_sec_traced": round(total_rows / on_s, 1),
    }
    from dask_ml_tpu.observability import MetricsLogger

    metrics_file = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_metrics.jsonl"
    )
    with MetricsLogger(metrics_file) as _lg:
        _lg.log(kind="bench_trace", **entry)
    return entry


def _bench_fleet(jax, on_tpu, n_chips):
    """Fleet section (ISSUE 6): 2-replica FleetServer vs a single
    ModelServer over the SAME ragged closed-loop mix, plus
    hot-swap-under-load — client-side p99 while 3 zero-recompile swaps
    land vs a swap-free steady-state pass on the same fleet.

    Replica throughput scaling is a DEVICE-parallelism story: with >1
    real device each replica's params and programs are committed to its
    own chip and XLA runs them concurrently (the >= 1.6x regime). On a
    shared-silicon CPU host both servers ride the same cores, so the
    honest ratio is ~1x — recorded as measured, per backend, exactly
    like the sentinel's backend-matched floors expect. The swap claim
    is backend-independent: p99 must NOT collapse while versions flip,
    because the swap mints zero compiles."""
    import threading as _threading
    import time

    from dask_ml_tpu import observability as obs
    from dask_ml_tpu.datasets import make_classification
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.serving import BucketLadder, FleetServer, ModelServer

    n = 100_000 if on_tpu else 20_000
    d = 128 if on_tpu else 32
    X, y = make_classification(n_samples=n, n_features=d,
                               n_informative=max(d // 4, 2),
                               random_state=0)
    X2, y2 = make_classification(n_samples=n, n_features=d,
                                 n_informative=max(d // 4, 2),
                                 random_state=7)
    a = LogisticRegression(solver="lbfgs", max_iter=20).fit(X, y)
    b = LogisticRegression(solver="lbfgs", max_iter=20).fit(X2, y2)
    Xh = X.to_numpy().astype(np.float32)

    rng = np.random.RandomState(11)
    n_requests = 400
    sizes = np.maximum(np.exp(
        rng.uniform(0, np.log(256), size=n_requests)
    ).astype(int), 1)
    offs = [int(rng.randint(0, n - s)) for s in sizes]
    requests = [Xh[i:i + int(s)] for s, i in zip(sizes, offs)]
    total_rows = int(sizes.sum())
    n_clients = 8
    shares = [list(range(c, n_requests, n_clients))
              for c in range(n_clients)]
    ladder = BucketLadder(8, 512, 2.0)

    def drive(server):
        """One closed-loop pass; returns (seconds, per-request secs)."""
        lats = np.zeros(n_requests)

        def client(c):
            for i in shares[c]:
                t1 = time.perf_counter()
                server.predict(requests[i])
                lats[i] = time.perf_counter() - t1

        threads = [_threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0, lats

    srv = ModelServer(a, methods=("predict",), ladder=ladder,
                      batch_window_ms=1.0, timeout_ms=0).warmup()
    with srv:
        drive(srv)                       # warm pass
        single_s, _ = drive(srv)

    fleet = FleetServer(a, name="bench", replicas=2, ladder=ladder,
                        batch_window_ms=1.0, timeout_ms=0).warmup()
    with fleet:
        drive(fleet)                     # warm pass
        fleet_s, steady_lats = drive(fleet)
        # hot-swap pass: same traffic while 3 publishes roll through
        before = obs.counters_snapshot().get("recompiles", 0)
        stop_swaps = _threading.Event()
        swaps = []

        def swapper():
            for est in (b, a, b):
                if stop_swaps.wait(0.05):
                    return
                swaps.append(fleet.publish(est))

        sw = _threading.Thread(target=swapper)
        sw.start()
        swap_s, swap_lats = drive(fleet)
        stop_swaps.set()
        sw.join()
        recompiles = obs.counters_snapshot().get("recompiles", 0) - before
        stats = fleet.stats()

    steady_p99 = float(np.percentile(steady_lats, 99))
    swap_p99 = float(np.percentile(swap_lats, 99))
    entries = _fleet_entries(jax, n_chips, n_requests, total_rows,
                             n_clients, single_s, fleet_s, swap_s,
                             steady_p99, swap_p99, swaps, recompiles,
                             stats)
    # the fleet numbers join the per-run record the headline fit opened
    from dask_ml_tpu.observability import MetricsLogger

    metrics_file = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_metrics.jsonl"
    )
    with MetricsLogger(metrics_file) as _lg:
        for e in entries:
            _lg.log(kind="bench_fleet", **e)
    return entries


def _fleet_entries(jax, n_chips, n_requests, total_rows, n_clients,
                   single_s, fleet_s, swap_s, steady_p99, swap_p99,
                   swaps, recompiles, stats):
    common = {
        "unit": "",
        "backend": jax.default_backend(),
        "dtype": "float32",
        "n_chips": n_chips,
        "replicas": 2,
        "n_requests": n_requests,
        "total_rows": total_rows,
        "n_clients": n_clients,
    }
    return [
        {
            **common,
            "metric": "fleet_2replica_throughput_rows_per_sec",
            "value": round(total_rows / fleet_s, 1),
            "unit": "rows/s",
            # replicas-vs-single on the same mix: ~1x on shared-silicon
            # CPU (see docstring), the >= 1.6x claim is per-device
            "vs_baseline": round(single_s / fleet_s, 3),
            "baseline": {
                "what": "single warmed ModelServer, same ragged mix",
                "seconds": round(single_s, 3),
                "rows_per_sec": round(total_rows / single_s, 1),
            },
            "fleet_seconds": round(fleet_s, 3),
        },
        {
            **common,
            "metric": "fleet_hot_swap_p99_seconds",
            "value": round(swap_p99, 4),
            "unit": "s",
            # the product claim: p99 under 3 rolling hot-swaps vs the
            # swap-free pass on the same fleet — flat, because the swap
            # compiles nothing
            "vs_baseline": round(swap_p99 / max(steady_p99, 1e-9), 3),
            "baseline": {
                "what": "steady-state p99 on the same 2-replica fleet, "
                        "no swaps",
                "p99_s": round(steady_p99, 4),
            },
            "swaps": len(swaps),
            "recompiles_during_swaps": int(recompiles),
            "swap_pass_seconds": round(swap_s, 3),
            "final_version": stats["version"],
        },
    ]


def _bench_federation(jax, on_tpu, n_chips):
    """Federation section (ISSUE 17): the same ragged closed-loop mix
    served through a :class:`FederatedFleet` router over two fleet
    processes (LocalEndpoints — the virtual-process transport, so the
    number measures ROUTING, not urllib), then a failover pass where
    one process dies mid-run: every admitted request must still
    resolve (``fleet_failover_lost_requests`` is recorded but, being
    0 by contract, never seeds a sentinel floor — the federation smoke
    gates it), plus the plans-warm autoscale spin-up latency
    (``ReplicaAutoscaler.scale_up`` returns it) against the same
    process's COLD first warmup."""
    import threading as _threading
    import time

    from dask_ml_tpu import observability as obs
    from dask_ml_tpu.datasets import make_classification
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.serving import (
        BucketLadder,
        FederatedFleet,
        FleetServer,
        LocalEndpoint,
        ReplicaAutoscaler,
        ServingError,
    )

    n = 100_000 if on_tpu else 20_000
    d = 128 if on_tpu else 32
    X, y = make_classification(n_samples=n, n_features=d,
                               n_informative=max(d // 4, 2),
                               random_state=0)
    a = LogisticRegression(solver="lbfgs", max_iter=20).fit(X, y)
    Xh = X.to_numpy().astype(np.float32)

    rng = np.random.RandomState(17)
    n_requests = 400
    sizes = np.maximum(np.exp(
        rng.uniform(0, np.log(256), size=n_requests)
    ).astype(int), 1)
    offs = [int(rng.randint(0, n - s)) for s in sizes]
    requests = [Xh[i:i + int(s)] for s, i in zip(sizes, offs)]
    total_rows = int(sizes.sum())
    n_clients = 8
    shares = [list(range(c, n_requests, n_clients))
              for c in range(n_clients)]
    ladder = BucketLadder(8, 512, 2.0)

    def drive(server):
        """One closed-loop pass; returns (seconds, lost-count)."""
        lost = [0] * n_clients

        def client(c):
            for i in shares[c]:
                try:
                    server.predict(requests[i])
                except ServingError:
                    lost[c] += 1

        threads = [_threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0, sum(lost)

    t0 = time.perf_counter()
    f0 = FleetServer(a, name="fed0", replicas=1, ladder=ladder,
                     batch_window_ms=1.0, timeout_ms=0).warmup()
    cold_warmup_s = time.perf_counter() - t0
    f1 = FleetServer(a, name="fed1", replicas=1, ladder=ladder,
                     batch_window_ms=1.0, timeout_ms=0).warmup()
    f0.start()
    f1.start()
    fed = FederatedFleet(
        [LocalEndpoint(f0, "p0"), LocalEndpoint(f1, "p1")],
        name="fed0", ladder=ladder, poll_s=0.1,
    ).start()
    try:
        drive(fed)                       # warm pass
        fed_s, _ = drive(fed)
        # failover pass: the ranked-first process dies mid-run; the
        # whole-request re-issue must lose nothing
        c0 = obs.counters_snapshot()
        victim = {"p0": f0, "p1": f1}[
            fed._ranked("predict", 64)[0].endpoint.process_id]
        killer = _threading.Timer(max(fed_s / 2, 0.05),
                                  lambda: victim.stop(drain=False))
        killer.start()
        failover_s, n_lost = drive(fed)
        killer.cancel()
        reroutes = obs.counters_snapshot() \
            .get("serving_process_reroutes", 0) \
            - c0.get("serving_process_reroutes", 0)
    finally:
        fed.stop()
        for f in (f0, f1):
            try:
                f.stop(drain=False)
            except Exception:
                pass

    # plans-warm spin-up: the same process has already compiled the
    # ladder, so scale_up's off-path warmup replays cached programs —
    # min over a few cycles (ms-scale timing, keep the floor stable)
    f2 = FleetServer(a, name="fed-scale", replicas=1, ladder=ladder,
                     batch_window_ms=1.0, timeout_ms=0).warmup().start()
    try:
        scaler = ReplicaAutoscaler(f2, min_replicas=1, max_replicas=4,
                                   interval_s=3600.0, patience=1,
                                   cooldown_s=0.0)
        spinups = []
        for _ in range(3):
            spinups.append(scaler.scale_up())
        warm_spinup_s = min(spinups)
    finally:
        f2.stop(drain=False)

    common = {
        "backend": jax.default_backend(),
        "dtype": "float32",
        "n_chips": n_chips,
        "processes": 2,
        "n_requests": n_requests,
        "total_rows": total_rows,
        "n_clients": n_clients,
    }
    entries = [
        {
            **common,
            "metric": "fleet_federated_rows_per_sec",
            "value": round(total_rows / fed_s, 1),
            "unit": "rows/s",
            "federated_seconds": round(fed_s, 3),
        },
        {
            **common,
            "metric": "fleet_failover_lost_requests",
            "value": int(n_lost),
            "unit": "requests",
            "criterion": "== 0 (whole-request re-issue on ProcessDown)",
            "criterion_met": n_lost == 0,
            "process_reroutes": int(reroutes),
            "failover_pass_seconds": round(failover_s, 3),
        },
        {
            **common,
            "metric": "autoscale_spinup_seconds",
            "value": round(warm_spinup_s, 4),
            "unit": "s",
            # plan-warm vs cold: the scale-up replays this process's
            # already-minted programs; the cold number is the same
            # ladder's first-ever warmup
            "vs_baseline": round(warm_spinup_s
                                 / max(cold_warmup_s, 1e-9), 4),
            "baseline": {
                "what": "cold 1-replica fleet warmup, same ladder",
                "seconds": round(cold_warmup_s, 3),
            },
            "spinups_s": [round(s, 4) for s in spinups],
        },
    ]
    from dask_ml_tpu.observability import MetricsLogger

    metrics_file = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_metrics.jsonl"
    )
    with MetricsLogger(metrics_file) as _lg:
        for e in entries:
            _lg.log(kind="bench_federation", **e)
    return entries


def _bench_fleet_observability(jax, on_tpu, n_chips):
    """Fleet observability section (ISSUE 19): what the fleet-scope
    planes cost, measured.

    - ``federated_scrape_seconds`` — one router poll tick with the
      metrics federator riding it: both processes' /status docs
      fetched (the SAME scrape routing uses — no second read), every
      counter/gauge/histogram folded into the fleet registry. This is
      the periodic off-path cost of ``obs_fleet_federate=True``.
    - ``federated_tracing_overhead_ratio`` — the same warmed
      closed-loop ragged mix through the ROUTER with the whole fleet
      plane on (trace propagation + per-leg continuation + federation)
      vs the all-defaults router. Host-side Python against this CPU
      backend's sub-ms steps is an adversarial denominator (same
      framing as ``request_trace_overhead_ratio``) — criterion >= 0.97
      on TPU, >= 0.60 here, floor-sentinel guarded."""
    import threading as _threading
    import time

    from dask_ml_tpu import config
    from dask_ml_tpu.datasets import make_classification
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.observability import traces_reset
    from dask_ml_tpu.serving import (
        BucketLadder,
        FederatedFleet,
        FleetServer,
        LocalEndpoint,
    )

    d = 32
    n = 20_000
    X, y = make_classification(n_samples=n, n_features=d,
                               n_informative=d // 4, random_state=0)
    clf = LogisticRegression(solver="lbfgs", max_iter=20).fit(X, y)
    Xh = X.to_numpy().astype(np.float32)

    rng = np.random.RandomState(23)
    n_requests = 400
    sizes = np.maximum(np.exp(
        rng.uniform(0, np.log(256), size=n_requests)
    ).astype(int), 1)
    offs = [int(rng.randint(0, n - s)) for s in sizes]
    requests = [Xh[i:i + int(s)] for s, i in zip(sizes, offs)]
    total_rows = int(sizes.sum())
    n_clients = 8
    shares = [list(range(c, n_requests, n_clients))
              for c in range(n_clients)]
    ladder = BucketLadder(8, 512, 2.0)

    def drive(fed):
        def client(c):
            for i in shares[c]:
                fed.predict(requests[i])

        threads = [_threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    def build(on):
        # federation + tracing captured at construction (the trace
        # gate and worker config are construction-time state)
        overrides = {"obs_drift": False}
        if on:
            overrides.update(obs_trace_sample=1.0, obs_trace_keep=64,
                             obs_fleet_federate=True)
        with config.set(**overrides):
            f0 = FleetServer(clf, name=f"fobs{int(on)}", replicas=1,
                             ladder=ladder, batch_window_ms=1.0,
                             timeout_ms=0).warmup().start()
            f1 = FleetServer(clf, name=f"fobs{int(on)}", replicas=1,
                             ladder=ladder, batch_window_ms=1.0,
                             timeout_ms=0).warmup().start()
            fed = FederatedFleet(
                [LocalEndpoint(f0, "p0"), LocalEndpoint(f1, "p1")],
                name=f"fobs{int(on)}", ladder=ladder, poll_s=3600.0,
            ).start()
        return fed, (f0, f1)

    fed_off, fleets_off = build(False)
    fed_on, fleets_on = build(True)
    try:
        # the scrape tick, isolated: min over repeats (µs-ms scale)
        scrapes = []
        for _ in range(20):
            t0 = time.perf_counter()
            fed_on._poll_once()
            scrapes.append(time.perf_counter() - t0)
        scrape_s = min(scrapes)

        # interleaved passes, each mode's best (shared-box confound
        # control, same as the request-trace section)
        drive(fed_off)                   # warm passes
        drive(fed_on)
        t_offs, t_ons = [], []
        for _ in range(4):
            t_offs.append(drive(fed_off))
            t_ons.append(drive(fed_on))
        off_s, on_s = min(t_offs), min(t_ons)
    finally:
        for fed, fleets in ((fed_off, fleets_off), (fed_on, fleets_on)):
            fed.stop()
            for f in fleets:
                try:
                    f.stop(drain=False)
                except Exception:
                    pass
    traces_reset()                       # no sampler state leaks
    ratio = off_s / on_s                 # >= 1.0 means no overhead
    thresh = 0.97 if on_tpu else 0.60
    common = {
        "backend": jax.default_backend(),
        "dtype": "float32",
        "processes": 2,
        "n_requests": n_requests,
        "total_rows": total_rows,
    }
    entries = [
        {
            **common,
            "metric": "federated_scrape_seconds",
            "value": round(scrape_s, 6),
            "unit": "s",
            "criterion": "off-path: one poll tick scrapes + merges "
                         "both processes' full telemetry",
            "scrapes_s": [round(s, 6) for s in scrapes[:5]],
        },
        {
            **common,
            "metric": "federated_tracing_overhead_ratio",
            "value": round(ratio, 4),
            "unit": "ratio",
            "criterion": f">= {thresh} (router + 2-leg trace "
                         "continuation + federation vs all-defaults "
                         "router; <= 3% on accelerator-scale steps)",
            "criterion_met": bool(ratio >= thresh),
            "rows_per_sec_plain": round(total_rows / off_s, 1),
            "rows_per_sec_observed": round(total_rows / on_s, 1),
        },
    ]
    from dask_ml_tpu.observability import MetricsLogger

    metrics_file = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_metrics.jsonl"
    )
    with MetricsLogger(metrics_file) as _lg:
        for e in entries:
            _lg.log(kind="bench_fleet_observability", **e)
    return entries


def _bench_incident_plane(jax, on_tpu, n_chips):
    """Incident plane section (ISSUE 20): what the alert engine costs,
    measured.

    - ``alert_tick_seconds`` — one full evaluation pass of a
      representative armed rule set (3 user rules + the 5 built-ins)
      over a populated counter/gauge registry: the engine's entire
      periodic cost (host dicts only — nothing else runs between
      ticks).
    - ``alerting_overhead_ratio`` — the same warmed closed-loop ragged
      mix through ONE ModelServer with the engine armed and ticking at
      a 20x-production cadence (0.25s vs the 5s default) vs disarmed —
      same server object, identical jaxprs, so the ratio isolates the
      ticker + registry contention. Criterion >= 0.97 on TPU, >= 0.60
      on this host-bound CPU backend, floor-sentinel guarded."""
    import threading as _threading
    import time

    from dask_ml_tpu import config
    from dask_ml_tpu.datasets import make_classification
    from dask_ml_tpu.linear_model import LogisticRegression
    from dask_ml_tpu.observability import alerts
    from dask_ml_tpu.observability.live import gauge_set
    from dask_ml_tpu.serving import BucketLadder, ModelServer

    d = 32
    n = 20_000
    X, y = make_classification(n_samples=n, n_features=d,
                               n_informative=d // 4, random_state=0)
    clf = LogisticRegression(solver="lbfgs", max_iter=20).fit(X, y)
    Xh = X.to_numpy().astype(np.float32)

    # -- the tick, isolated: a detached engine (no thread) driven by
    # hand over a registry populated the way a serving process's is
    for i in range(16):
        gauge_set(f"bench_plane_gauge_{i}", float(i))
    rules = alerts.parse_rules(
        "serving_slo_violations:rate>5/60s,"
        "bench_plane_gauge_3:gauge>1e9,"
        "serving_requests:counter>=1000000000"
    )
    rules.extend(alerts._builtin_rules())
    eng = alerts.AlertEngine(rules, 3600.0)
    ticks = []
    for _ in range(200):
        t0 = time.perf_counter()
        eng.tick()
        ticks.append(time.perf_counter() - t0)
    tick_s = min(ticks)

    rng = np.random.RandomState(29)
    n_requests = 400
    sizes = np.maximum(np.exp(
        rng.uniform(0, np.log(256), size=n_requests)
    ).astype(int), 1)
    offs = [int(rng.randint(0, n - s)) for s in sizes]
    requests = [Xh[i:i + int(s)] for s, i in zip(sizes, offs)]
    total_rows = int(sizes.sum())
    n_clients = 8
    shares = [list(range(c, n_requests, n_clients))
              for c in range(n_clients)]

    def drive(srv):
        def client(c):
            for i in shares[c]:
                srv.submit(requests[i]).result(60)

        threads = [_threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    # ONE server serves both modes (the plane is pure host-side — the
    # serving jaxprs are byte-identical either way, asserted in
    # tests/test_incident_plane.py); the singleton engine arms/disarms
    # around each ON pass, interleaved best-of as everywhere else
    with config.set(obs_drift=False):
        srv = ModelServer(clf, ladder=BucketLadder(8, 512, 2.0),
                          batch_window_ms=1.0, timeout_ms=0)
        srv.warmup()
        try:
            with srv:
                drive(srv)               # warm pass
                t_offs, t_ons = [], []
                for _ in range(4):
                    t_offs.append(drive(srv))
                    with config.set(
                        obs_alert_rules="serving_slo_violations:"
                                        "rate>1000000/60s",
                        obs_alert_interval_s=0.25,
                    ):
                        assert alerts.ensure_engine() is not None
                        t_ons.append(drive(srv))
                        alerts.stop_engine()
                off_s, on_s = min(t_offs), min(t_ons)
        finally:
            alerts.reset()
    ratio = off_s / on_s                 # >= 1.0 means no overhead
    thresh = 0.97 if on_tpu else 0.60
    common = {
        "backend": jax.default_backend(),
        "dtype": "float32",
        "n_requests": n_requests,
        "total_rows": total_rows,
    }
    entries = [
        {
            **common,
            "metric": "alert_tick_seconds",
            "value": round(tick_s, 6),
            "unit": "s",
            "n_rules": len(rules),
            "criterion": "off-path: one evaluation pass over the live "
                         "registry (3 user rules + 5 built-ins), host "
                         "dicts only",
        },
        {
            **common,
            "metric": "alerting_overhead_ratio",
            "value": round(ratio, 4),
            "unit": "ratio",
            "criterion": f">= {thresh} (same warmed server, engine "
                         "armed @0.25s tick vs disarmed; <= 3% on "
                         "accelerator-scale steps)",
            "criterion_met": bool(ratio >= thresh),
            "rows_per_sec_plain": round(total_rows / off_s, 1),
            "rows_per_sec_alerting": round(total_rows / on_s, 1),
        },
    ]
    from dask_ml_tpu.observability import MetricsLogger

    metrics_file = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_metrics.jsonl"
    )
    with MetricsLogger(metrics_file) as _lg:
        for e in entries:
            _lg.log(kind="bench_incident_plane", **e)
    return entries


_emit_lock = threading.Lock()
_emitted = False
# progressive results for the watchdog: headline result + extras list
_partial = {"result": None, "extras": []}


def _emit(result) -> None:
    """Print the one JSON line exactly once, even if the watchdog and the
    main thread race at the deadline."""
    global _emitted
    with _emit_lock:
        if _emitted:
            return
        _emitted = True
        print(json.dumps(result), flush=True)


def _error_result(msg):
    return {
        "metric": "logreg_fit_samples_per_sec_per_chip",
        "value": None,
        "unit": "samples/s/chip",
        "vs_baseline": None,
        "error": msg,
    }


def _deadline_result(msg):
    """Best result available at a deadline: the completed headline (plus
    whatever extras finished), marked truncated — else the error line."""
    if _partial["result"] is not None:
        out = dict(_partial["result"])
        out["extra_metrics"] = list(_partial["extras"])
        out["truncated"] = msg
        return out
    return _error_result(msg)


def _start_watchdog():
    """Daemon thread that emits a JSON line and hard-exits non-zero if
    the bench overruns BENCH_TOTAL_TIMEOUT. A thread (not SIGALRM)
    because a hang inside native XLA code never returns to the bytecode
    loop, so a Python signal handler would never run."""

    def watch_total():
        time.sleep(_TOTAL_TIMEOUT)
        _emit(_deadline_result(
            f"watchdog: exceeded BENCH_TOTAL_TIMEOUT={_TOTAL_TIMEOUT}s"
        ))
        os._exit(3)

    threading.Thread(target=watch_total, daemon=True).start()


def _last_json_line(text):
    """Last stdout line that parses as a metric JSON object, else None."""
    for line in reversed((text or "").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if isinstance(obj, dict) and "metric" in obj:
                return obj
    return None


def main():
    """The benchmark, in this process, on the backend jax gives it. The
    two BENCH_*_CHILD modes are the CPU sections' virtual-device
    grandchildren (forced to the CPU platform — they never need a
    chip); a TPU run spawns nothing."""
    if os.environ.get("BENCH_SHARDED_CHILD"):
        _sharded_child_main()
        return
    if os.environ.get("BENCH_MESH2D_CHILD"):
        _mesh2d_child_main()
        return
    _start_watchdog()
    try:
        result = run()
    except BaseException as exc:  # still one JSON line, then the error
        _emit(_deadline_result(f"{type(exc).__name__}: {exc}"))
        raise
    _emit(result)
    failed = [e["metric"] for e in result["extra_metrics"]
              if e.get("error")]
    if failed:
        sys.stderr.write(f"bench: sections failed: {failed}\n")
        sys.exit(1)


if __name__ == "__main__":
    main()
