"""Streamed distributed randomized SVD (ISSUE 18 tentpole, layer 3).

Reference equivalent: ``da.linalg.svd_compressed`` (Halko) over
host-backed chunks (SURVEY.md §3.3) — the reference's range finder is a
task graph of blockwise matmuls + TSQR reductions. Here each range pass
is ONE streamed super-block scan (``BlockStream.superblocks()``: K
stacked blocks per XLA dispatch, DONATED carry, zero compiles after
pass 1) and the tall factor never materializes: the scan carries

- ``Z = Σ_b Xc_bᵀ Y_b``  (d, k') — the next subspace, and
- ``R``  (k', k') — the blocked-QR / TSQR R-factor of the tall
  ``Y = Xc @ Ω``, reduced over the mesh's "data" axis,

so device memory is O(d·k') while the resident ``ops.linalg`` path
holds the full (n, d) matrix. On a 2-D ("data", "model") mesh the X
super-blocks stage as (rows/D, d/M) per-device tiles and the programs
add "model" psums exactly where the math contracts over features
(``Y_b = Σ_m X_m @ Ω_m`` and the Z/moment reassembly) — the
``superblock.pca.*.model_psum`` flavor.

Pass structure (``n_iter`` power iterations, matching the resident
``randomized_svd``):

1. ``superblock.pca.moments`` — shift-centered (Σx, Σx²) for the mean
   and per-feature variance (explained-variance ratios);
2. ``n_iter + 1`` × ``superblock.pca.range`` — each pass applies XᵀX
   to the current basis in ONE sweep (Y_b and Xᵀ Y_b from the same
   staged block); between passes the host orthonormalizes
   ``Ω ← qr(Z R⁻¹).Q`` (Halko's re-orthonormalized power step; Z and
   R are (d,k')/(k',k') — client-sized, like the reference's small
   collect);
3. the LAST pass doubles as the extraction: ``Y = Xc Ω`` with Ω
   orthonormal gives ``svd(R) = U_r S V_rᵀ`` and
   ``components = (Ω V_r)ᵀ`` — no extra projection pass over the data.

Total passes: ``n_iter + 2``. Every dispatch of a pass hits one
compiled program (fixed [K, block_rows, d] operands, ragged tail
padded with zero counts — zero rows leave both Z and the R-factor
unchanged, the same invariant ``ops.linalg.tsqr`` relies on).
"""

from __future__ import annotations

import functools as _ft

import jax
import jax.numpy as jnp
import numpy as np

from ..plans import ProgramPlan, warmups

# d at which the streamed Gram path's d×d covariance (f64 host + f32
# device per block) stops being the cheap one-pass answer and the
# O(d·k') randomized path takes over for solver="auto" fits
STREAM_GRAM_MAX_D = 4096


def _qr_r(a):
    """R-factor of ``a`` (rows >= cols after stacking), shape-stable
    (k', k') — the one blocked-QR step both the scan chain and the
    cross-shard TSQR combine use."""
    return jnp.linalg.qr(a)[1]


@_ft.lru_cache(maxsize=64)
def _pca_reducer(kind, mesh=None, model_shards=1):
    """The donated-carry super-block program for one rSVD pass flavor.

    ``kind``:
      - "moments": ``run(acc=(s1, s2), shift, Xs, counts)`` —
        shift-centered per-feature (Σc, Σc²) sums;
      - "range":   ``run(acc=(Z, R), mean, omega, Xs, counts)`` —
        ``Z += Xc_bᵀ (Xc_b Ω)`` and the blocked-QR chain
        ``R ← qr([R; Y_b]).R`` per block.

    ``mesh`` selects the shard_map flavor (replicated carry, per-shard
    row slabs, TSQR combine of the per-shard R chains over "data");
    ``model_shards > 1`` the feature-sharded flavor (per-device
    (K, S/D, d/M) X tiles, "model" psums at the feature contractions).
    Cached per flavor — every pass of every fit reuses ONE jitted
    callable, so steady-state fits pay zero XLA compiles (asserted in
    perf_smoke)."""
    if mesh is not None:
        return _pca_reducer_sharded(kind, mesh, model_shards)

    if kind == "moments":
        def body(acc, shift, Xs, counts):
            r = jnp.arange(Xs.shape[1])

            def step(a, Xb, c):
                mask = (r < c).astype(Xb.dtype)
                cb = (Xb - shift) * mask[:, None]
                return (a[0] + jnp.sum(cb, axis=0),
                        a[1] + jnp.sum(cb * cb, axis=0))

            def scan_step(a, inp):
                return step(a, *inp), jnp.float32(0.0)

            acc, _ = jax.lax.scan(scan_step, acc, (Xs, counts))
            return acc
    else:
        def body(acc, mean, omega, Xs, counts):
            r = jnp.arange(Xs.shape[1])

            def step(a, Xb, c):
                Z, R = a
                mask = (r < c).astype(Xb.dtype)
                cb = (Xb - mean) * mask[:, None]
                Yb = cb @ omega
                return (Z + cb.T @ Yb,
                        _qr_r(jnp.concatenate([R, Yb], axis=0)))

            def scan_step(a, inp):
                return step(a, *inp), jnp.float32(0.0)

            acc, _ = jax.lax.scan(scan_step, acc, (Xs, counts))
            return acc

    return ProgramPlan(
        name=f"superblock.pca.{kind}", body=body, donate=(0,),
        key=("pca-stream", kind, None, 1), group="superblock",
    ).build()


def _pca_reducer_sharded(kind, mesh, model_shards):
    """shard_map flavor of :func:`_pca_reducer`: each device scans its
    own row slab (and, feature-sharded, its own d/M feature tile) of
    every block; carries and the Ω/mean operands stay REPLICATED. Per
    super-block the "data" collectives are exactly two psums — the
    local Z/moment delta and the TSQR gather of the per-shard R
    chains; "model" psums appear only where the math contracts over
    features (the per-block feature-dot ``Y_b = Σ_m X_m Ω_m`` and the
    final slice reassembly), mirroring the GLM
    ``_sb_reducer_feature_sharded`` structure."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

    M = int(model_shards)

    def _x_spec(a, lead):
        # X tiles: rows over "data", features (last axis) over "model"
        return P(*((None,) * lead + (DATA_AXIS,)
                   + (None,) * (a.ndim - lead - 2)
                   + (MODEL_AXIS if M > 1 else None,)))

    def _feat_slice(full, dm):
        # this device's feature slice of a replicated (d, ...) operand
        mi = jax.lax.axis_index(MODEL_AXIS)
        if full.ndim == 1:
            return jax.lax.dynamic_slice(full, (mi * dm,), (dm,))
        return jax.lax.dynamic_slice(
            full, (mi * dm, 0), (dm, full.shape[1])
        )

    def _scatter_feat(t):
        # feature-tile -> replicated full width: scatter into a zero
        # (d, ...) buffer at this device's offset, psum over "model"
        # (exact — adds zeros)
        mi = jax.lax.axis_index(MODEL_AXIS)
        dm = t.shape[0]
        full = (dm * M,) + t.shape[1:]
        start = (mi * dm,) + (0,) * (t.ndim - 1)
        z = jax.lax.dynamic_update_slice(jnp.zeros(full, t.dtype), t,
                                         start)
        return jax.lax.psum(z, MODEL_AXIS)

    def _gather_data(t):
        # per-shard (k', k') R chains -> replicated (D*k', k') stack:
        # the TSQR combine's scatter+psum over "data"
        di = jax.lax.axis_index(DATA_AXIS)
        k = t.shape[0]
        D = mesh.shape[DATA_AXIS]
        z = jax.lax.dynamic_update_slice(
            jnp.zeros((D * k,) + t.shape[1:], t.dtype), t,
            (di * k,) + (0,) * (t.ndim - 1),
        )
        return jax.lax.psum(z, DATA_AXIS)

    if kind == "moments":
        def body(acc, shift, Xs, counts):
            r = jnp.arange(Xs.shape[1])
            cts = counts[0]
            dm = Xs.shape[-1]
            sh = _feat_slice(shift, dm) if M > 1 else shift
            local = (jnp.zeros((dm,), jnp.float32),
                     jnp.zeros((dm,), jnp.float32))

            def step(a, Xb, c):
                mask = (r < c).astype(Xb.dtype)
                cb = (Xb - sh) * mask[:, None]
                return (a[0] + jnp.sum(cb, axis=0),
                        a[1] + jnp.sum(cb * cb, axis=0))

            def scan_step(a, inp):
                return step(a, *inp), jnp.float32(0.0)

            local, _ = jax.lax.scan(scan_step, local, (Xs, cts))
            local = jax.lax.psum(local, DATA_AXIS)
            if M > 1:
                local = tuple(_scatter_feat(t) for t in local)
            return tuple(a + l for a, l in zip(acc, local))

        def run_body(acc, shift, Xs, counts):
            xs_spec = _x_spec(Xs, 1)
            f = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(), xs_spec, P(DATA_AXIS, None)),
                out_specs=P(),
                check_vma=False,
            )
            return f(acc, shift, Xs, counts)
    else:
        def body(acc, mean, omega, Xs, counts):
            r = jnp.arange(Xs.shape[1])
            cts = counts[0]
            dm = Xs.shape[-1]
            kp = omega.shape[1]
            if M > 1:
                mn, om = _feat_slice(mean, dm), _feat_slice(omega, dm)
            else:
                mn, om = mean, omega
            Z0 = jnp.zeros((dm, kp), jnp.float32)
            R0 = jnp.zeros((kp, kp), jnp.float32)

            def step(a, Xb, c):
                Zl, Rl = a
                mask = (r < c).astype(Xb.dtype)
                cb = (Xb - mn) * mask[:, None]
                Yb = cb @ om
                if M > 1:  # the feature-dot: eta-style psum over model
                    Yb = jax.lax.psum(Yb, MODEL_AXIS)
                return (Zl + cb.T @ Yb,
                        _qr_r(jnp.concatenate([Rl, Yb], axis=0)))

            local = (Z0, R0)

            def scan_step(a, inp):
                return step(a, *inp), jnp.float32(0.0)

            local, _ = jax.lax.scan(scan_step, local, (Xs, cts))
            Zl, Rl = local
            Zd = jax.lax.psum(_scatter_feat(Zl) if M > 1 else Zl,
                              DATA_AXIS)
            # TSQR combine over "data": the replicated running R chain
            # stacked on every shard's local chain, one small QR
            Rs = _gather_data(Rl)
            Rn = _qr_r(jnp.concatenate([acc[1], Rs], axis=0))
            return (acc[0] + Zd, Rn)

        def run_body(acc, mean, omega, Xs, counts):
            xs_spec = _x_spec(Xs, 1)
            f = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(), P(), xs_spec, P(DATA_AXIS, None)),
                out_specs=P(),
                check_vma=False,
            )
            return f(acc, mean, omega, Xs, counts)

    from ..parallel.mesh import mesh_str

    suffix = ".model_psum" if M > 1 else ".psum"
    return ProgramPlan(
        name=f"superblock.pca.{kind}{suffix}", body=run_body,
        donate=(0,), key=("pca-stream", kind, mesh, M),
        group="superblock", mesh=mesh_str(mesh),
    ).build()


def _orth_next(Z, R):
    """Host half-iteration: ``Ω_next = qr(Z R⁻¹).Q`` — the
    re-orthonormalized power step (span(Z R⁻¹) = span(Xᵀ Q_y)). Falls
    back to the pseudo-inverse when the chain's R is rank-deficient
    (degenerate spectra); qr still returns a full orthonormal basis."""
    import scipy.linalg as sla

    try:
        w = sla.solve_triangular(R.T, Z.T, lower=True).T
    except Exception:
        w = None
    if w is None or not np.all(np.isfinite(w)):
        w = Z @ np.linalg.pinv(R)
    return np.linalg.qr(w)[0]


def streamed_randomized_svd(X, block_rows, size, n_iter, key, *,
                            center=True, n_rows_global=None):
    """Run the streamed rSVD passes over ``X`` (see module docstring).

    Returns a dict: ``s`` (size,) singular values (desc), ``vt``
    (size, d) right singular vectors, ``mean`` (d,) f64 data mean,
    ``var0``/``var1`` (d,) f64 per-feature variance (ddof 0 / 1),
    ``n`` global rows, ``passes`` data passes consumed, ``stream``
    (for ``profile_snapshot``). ``center=False`` (TruncatedSVD) keeps
    the SVD uncentered but still returns the moment statistics.
    Multi-process: moments/Z merge via ``psum_host``, the R chains via
    a host TSQR combine, so every process sees the identical global
    decomposition."""
    from ..parallel import distributed as dist
    from ..parallel.streaming import BlockStream, _slice_dense

    n_local, d = int(X.shape[0]), int(X.shape[1])
    multi = dist.process_count() > 1
    n = int(n_rows_global) if n_rows_global is not None else (
        int(dist.psum_host(np.asarray(float(n_local)))) if multi
        else n_local
    )
    stream = BlockStream((X,), block_rows=block_rows)
    sharded = stream.sb_sharded()
    D = stream.sb_data_shards()
    M = stream.sb_model_shards()
    mesh = stream.mesh if sharded else None

    def _put(acc):
        if not sharded:
            return acc
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(acc, NamedSharding(stream.mesh, P()))

    def _note(kind, run):
        suffix = (".model_psum" if M > 1 else ".psum") if sharded \
            else ""
        warmups.note(
            ("pca-stream", kind, d, int(size), D, M),
            program=f"superblock.pca.{kind}{suffix}", ran=True,
        )
        return run

    # shift estimate (identical on every process — see PCA._fit_streamed)
    head = _slice_dense(X, 0, min(4096, n_local), np.float64)
    if multi:
        hs, hn = dist.psum_host(head.sum(axis=0),
                                np.asarray(float(len(head))))
        shift = hs / max(float(hn), 1.0)
    else:
        shift = head.mean(axis=0) if len(head) else np.zeros(d)

    # pass 0: moments (mean + per-feature variance)
    run = _note("moments", _pca_reducer("moments", mesh=mesh,
                                        model_shards=M))
    acc = _put((jnp.zeros((d,), jnp.float32),
                jnp.zeros((d,), jnp.float32)))
    shift_dev = jnp.asarray(shift, jnp.float32)
    for sb in stream.superblocks():
        cts = sb.shard_counts if sharded else sb.counts
        acc = run(acc, shift_dev, sb.arrays[0], cts)
    s1 = np.asarray(acc[0], np.float64)
    s2 = np.asarray(acc[1], np.float64)
    if multi:
        s1, s2 = dist.psum_host(s1, s2)
    mean_c = s1 / n
    mean = shift + mean_c
    var0 = np.maximum(s2 / n - mean_c * mean_c, 0.0)
    var1 = np.maximum((s2 - s1 * s1 / n) / max(n - 1, 1), 0.0)

    # range passes: n_iter power iterations + the extraction pass
    mean_dev = jnp.asarray(mean if center else np.zeros(d), jnp.float32)
    omega = np.asarray(
        jax.random.normal(key, (d, int(size)), jnp.float32)
    )
    n_range = max(int(n_iter), 1) + 1
    run = _note("range", _pca_reducer("range", mesh=mesh,
                                      model_shards=M))
    Z = R = None
    for p in range(n_range):
        acc = _put((jnp.zeros((d, int(size)), jnp.float32),
                    jnp.zeros((int(size), int(size)), jnp.float32)))
        omega_dev = jnp.asarray(omega, jnp.float32)
        for sb in stream.superblocks():
            cts = sb.shard_counts if sharded else sb.counts
            acc = run(acc, mean_dev, omega_dev, sb.arrays[0], cts)
        Z = np.asarray(acc[0], np.float64)
        R = np.asarray(acc[1], np.float64)
        if multi:
            Z = dist.psum_host(Z)
            rs = dist.allgather_object(np.asarray(R))
            R = np.linalg.qr(np.concatenate(rs, axis=0))[1]
        if p < n_range - 1:
            omega = _orth_next(Z, R).astype(np.float32)

    # extraction: Y = Xc Ω (Ω orthonormal) = Q R, svd(R) = U_r S V_rᵀ
    # → X ≈ (Q U_r) S (Ω V_r)ᵀ; the small factors are client-sized
    _, s, vt_r = np.linalg.svd(R)
    vt = (omega.astype(np.float64) @ vt_r.T).T
    return {
        "s": s, "vt": vt, "mean": mean, "var0": var0, "var1": var1,
        "n": n, "passes": 1 + n_range, "stream": stream,
    }


def flip_signs_vt(vt):
    """Deterministic component signs, V-based (the ``linalg.svd_flip``
    convention on host f64): each row's largest-|.| entry positive."""
    max_abs = np.argmax(np.abs(vt), axis=1)
    signs = np.sign(vt[np.arange(vt.shape[0]), max_abs])
    return vt * np.where(signs == 0, 1.0, signs)[:, None]
