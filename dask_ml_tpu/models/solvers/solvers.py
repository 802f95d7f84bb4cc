"""GLM solvers: lbfgs, gradient_descent, newton, proximal_grad, admm.

Reference equivalent: ``dask_glm/algorithms.py`` (SURVEY.md §2b row 6,
§3.2). The reference keeps optimizer state on the *client* and pays a full
cluster round-trip per function evaluation (scipy's Fortran L-BFGS-B driving
dask graphs). The TPU design inverts that (SURVEY.md §7 design stance #2):

- Solver state lives ON DEVICE. Each solver is a single jitted program whose
  outer iteration is a ``lax.while_loop``; line searches
  (Armijo backtracking, optax zoom) are inner ``while_loop``s. Host sees one
  scalar diagnostics tuple at the end — zero per-iteration round-trips.
- Data parallelism is implicit: X is row-sharded, so ``X @ beta`` /
  ``X.T @ r`` lower to per-shard matmuls + ICI psum (the reference's
  tree-reduce, without the task graph).
- ADMM runs per-shard local Newton solves inside ``shard_map`` with a psum
  consensus z-update — the reference gathers per-chunk betas to the client
  and broadcasts z back over TCP each outer iteration.

All jitted entry points are module-level with static (family, reg) names so
XLA's compile cache is shared across estimator instances.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.scipy.linalg import cho_solve
from jax.sharding import PartitionSpec as P

from ...base import to_host
from ...parallel.mesh import DATA_AXIS
from ...observability import current_span, emit_jit_step, track_program
from ...plans import ProgramPlan
from ..solvers import regularizers
from ..solvers.families import get_family


def _smooth_loss(beta, X, y, mask, n_rows, lam, pmask, l1_ratio, family, reg,
                 intercept=False):
    """Mask-weighted mean NLL + smooth penalty. One psum under jit.

    The matvec casts beta to X's dtype with f32 accumulation, so a bf16
    design matrix (config.dtype="bfloat16") runs the MXU at bf16 rate
    while the loss/penalty stay f32.

    ``intercept`` (static): the intercept is the LAST ENTRY OF BETA,
    added to eta in f32 — X is ``(n, d)`` for a ``(d + 1,)`` beta and
    carries no ones column (a 257th column relays a 256-wide bf16 design
    out of its row-major layout and pads it to 384 lanes on a TPU).
    Autodiff gives its gradient, Σ resid * mask; padding rows see
    ``eta = beta[-1]`` and are masked as before."""
    coef = beta[:-1] if intercept else beta
    eta = jax.lax.dot_general(
        X, coef.astype(X.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if intercept:
        eta = eta + beta[-1]
    base = jnp.sum(get_family(family).pointwise(eta, y) * mask) / n_rows
    return base + regularizers.value(reg, beta, lam, pmask, l1_ratio)


def _pallas_loss(X, y, mask, n_rows, lam, pmask, l1_ratio, family, reg,
                 mesh, interpret, intercept=False):
    """Smooth loss whose DATA term's value and gradient both come from
    the fused Pallas kernel (``ops/pallas_fused.fused_glm_value_grad``):
    one X pass per value_and_grad instead of XLA's two (forward matvec +
    gradient matmul) — the GLM fit is HBM-bound, so this halves the
    traffic of every solver iteration. The kernel runs per shard inside
    shard_map with a psum merge; a custom_vjp hands autodiff the
    kernel's gradient, and the penalty/mean scaling stay ordinary XLA on
    the (d,) vector. ``intercept``: ``_smooth_loss``'s contract — the
    kernel takes ``beta[-1]`` as its scalar operand and returns that
    entry's gradient beside the (d,) one."""
    from ...ops.pallas_fused import fused_glm_value_grad

    def per_shard(bs, xs, ys, ms, nv):
        if not intercept:
            return fused_glm_value_grad(xs, nv, ys, bs, family=family,
                                        interpret=interpret)
        v, g, gb = fused_glm_value_grad(
            xs, nv, ys, bs[:-1], family=family, interpret=interpret,
            intercept=bs[-1],
        )
        # one (d + 1,) gradient BEFORE the psum: as many all-reduces an
        # evaluation as the column form had
        return v, jnp.concatenate([g, gb[None]])

    def data_vg(beta):
        return _shard_psum_call(mesh, per_shard, 2, beta, X, y, mask)

    return _custom_vjp_loss(data_vg, n_rows, reg, lam, pmask, l1_ratio)


def _shard_psum_call(mesh, per_shard, n_out, beta, X, y, mask):
    """Run a per-shard GLM kernel under shard_map and psum its
    ``n_out`` partial outputs — the ONE copy of the (specs, prefix
    valid-row count, psum) sharding contract used by every fused
    solver path."""
    def shard(bs, xs, ys, ms):
        nv = jnp.sum(ms.astype(jnp.int32))
        outs = per_shard(bs, xs, ys, ms, nv)
        return tuple(jax.lax.psum(o, DATA_AXIS) for o in outs)

    f = jax.shard_map(
        shard, mesh=mesh,
        in_specs=(P(), P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=tuple(P() for _ in range(n_out)),
        check_vma=False,
    )
    return f(beta, X, y, mask)


def _custom_vjp_loss(data_vg, n_rows, reg, lam, pmask, l1_ratio):
    """Wrap a kernel-backed ``beta -> (value, grad)`` into a scalar loss
    whose autodiff uses the kernel's gradient (custom_vjp), plus the
    penalty/mean scaling in XLA — the ONE copy of this scaffolding,
    shared by the single- and multi-target Pallas paths."""

    @jax.custom_vjp
    def data_sum(beta):
        v, _ = data_vg(beta)
        return v

    def fwd(beta):
        return data_vg(beta)

    def bwd(g, ct):
        return (ct * g,)

    data_sum.defvjp(fwd, bwd)

    def loss(beta):
        return data_sum(beta) / n_rows + regularizers.value(
            reg, beta, lam, pmask, l1_ratio
        )

    return loss


def _select_loss(use_pallas, X, y, mask, n_rows, lam, pmask, l1_ratio,
                 family, reg, mesh, interpret, intercept=False):
    """The ONE place a jitted solver body picks its smooth loss: the
    fused Pallas value+grad (one X pass per evaluation) or the plain
    XLA objective. Both read the static ``intercept`` the same way
    (``_smooth_loss``): the last entry of beta, not a column of X."""
    if use_pallas:
        return _pallas_loss(X, y, mask, n_rows, lam, pmask, l1_ratio,
                            family, reg, mesh, interpret, intercept)
    return partial(_smooth_loss, X=X, y=y, mask=mask, n_rows=n_rows,
                   lam=lam, pmask=pmask, l1_ratio=l1_ratio,
                   family=family, reg=reg, intercept=intercept)


def _resolve_pallas(use_pallas, mesh, family, X=None):
    """Auto gate for the fused GLM kernel: real TPU backend, a plain
    data-parallel mesh (feature-sharded TP layouts keep the GSPMD
    path), known family, and a design narrow enough that a row tile
    fits the kernel's VMEM budget (wide designs keep the XLA loss,
    whose matmuls tile the feature dim freely)."""
    if use_pallas is not None:
        return bool(use_pallas)
    from ...parallel.mesh import MODEL_AXIS
    from ...ops.pallas_fused import glm_tile

    return (
        jax.default_backend() == "tpu"
        and mesh is not None
        and mesh.shape.get(MODEL_AXIS, 1) == 1
        and family in ("logistic", "normal", "poisson")
        and (X is None or glm_tile(
            X.shape[0], X.shape[1], X.dtype.itemsize
        ) is not None)
    )


@jax.jit
def _pack_scalars(*vals):
    return jnp.stack([jnp.asarray(v, jnp.float32) for v in vals])


def _host_scalars(*vals):
    """Fetch a handful of device result scalars in ONE launch and ONE
    device→host transfer — separate int()/float() pulls each pay a host
    round trip and a sync, and an eager cast per value is a launch of its
    own behind the solve (~1 ms each on a mesh of four). This is where
    the host waits for the whole solve: the wait is charged to the open
    span (``fit.solve``) as ``sync_s``."""
    return _fetch(_pack_scalars(*vals))[0]


def _fetch(*vals):
    """A program's results to the host in ONE ``jax.device_get``, with no
    launch in front of it (what the host reads is among the program's
    own outputs). The copies are queued behind the program before the
    host waits; the wait is the open span's ``sync_s`` and the read one
    fetch of its ledger."""
    sp = current_span()
    for v in vals:
        v.copy_to_host_async()
    return sp.fetch(jax.device_get, sp.sync(vals))


def _operand(v, dtype=np.float32):
    """A small operand as the solver's jit takes it. A host value stays a
    host value of ``dtype`` and rides in with the program's dispatch:
    ``jnp.asarray`` of it is an eager launch and a transfer of its own,
    on every device of the mesh, with the chip idle (same aval either
    way, so the same program). A device array is handed on."""
    if isinstance(v, jax.Array):
        return v if v.dtype == dtype else v.astype(dtype)
    return np.asarray(v, dtype)


def check_finite_result(beta, info, solver):
    """NaN/Inf sanitizer (SURVEY.md §5 race-detection row): a NaN ends a
    ``gnorm > tol`` while_loop as "converged", silently. Every solver
    funnels its result through here; non-finite parameters raise instead
    of becoming a model."""
    beta_h = to_host(beta)  # the one beta fetch — callers reuse it
    scalars = [v for v in info.values() if isinstance(v, (int, float))]
    if not np.isfinite(beta_h).all() or not np.all(np.isfinite(scalars)):
        raise FloatingPointError(
            f"solver {solver!r} produced non-finite parameters "
            f"(info={info}): the input contains NaN/Inf or the solve "
            f"diverged — validate the data or reduce the step size / C"
        )
    return beta_h, info


def _check_smooth(reg, solver):
    if reg not in regularizers.SMOOTH:
        raise ValueError(
            f"solver {solver!r} handles smooth penalties only (l2/none), got "
            f"{reg!r}; use solver='proximal_grad' or 'admm' for l1/elastic_net"
        )


# --------------------------------------------------------------------------
# L-BFGS (optax, zoom linesearch) — whole optimization in one XLA program
# --------------------------------------------------------------------------

@track_program("glm.lbfgs")
@partial(jax.jit, static_argnames=("family", "reg", "memory", "log",
                                   "use_pallas", "mesh", "interpret",
                                   "intercept"))
def _lbfgs_chunk(X, y, mask, n_rows, carry, lam, pmask, l1_ratio, stop_it,
                 tol, family, reg, memory=10, log=False, use_pallas=False,
                 mesh=None, interpret=False, intercept=False):
    """Run the L-BFGS while_loop from ``carry`` until ``stop_it`` (or
    convergence). A full solve is one chunk with stop_it = max_iter; the
    checkpointed path runs k-iteration chunks so (beta, optimizer state)
    hits stable storage between programs (SURVEY.md §5 checkpoint row —
    TPU slices fail whole, recovery is checkpoint-restart).

    ``carry`` is ``(beta0,)`` for a fresh start — the program builds its
    own first state (``_lbfgs_loop``) — or a whole carry to continue
    from; the pytree's structure is part of the jit's cache key, so these
    are two programs over one loop. Returns ``(carry, result)``:
    ``result`` is what the host reads of a solve, ``[*beta, it, gnorm,
    n_evals]`` as one vector, so that it leaves in one fetch (the
    counters are exact in float32 up to 2**24)."""
    loss = _select_loss(use_pallas, X, y, mask, n_rows, lam, pmask,
                        l1_ratio, family, reg, mesh, interpret, intercept)
    carry = _lbfgs_loop(loss, carry, stop_it, tol, memory, log)
    beta, _, gnorm, it, n_evals = carry
    scalars = jnp.stack([jnp.asarray(v, beta.dtype)
                         for v in (it, gnorm, n_evals)])
    return carry, jnp.concatenate([beta, scalars])


def _fresh_carry(opt, beta0):
    """The carry an L-BFGS solve starts from: ``(beta0, the optimizer's
    empty state, gnorm = inf, it = 0)``. Built INSIDE the solver's
    program from a one-element carry (``_lbfgs_loop``): built on the
    host it is a dozen eager launches (every leaf of the optax state a
    ``zeros`` or a cast of its own) with the chip idle."""
    return (beta0, opt.init(beta0), jnp.asarray(jnp.inf, beta0.dtype), 0)


def _lbfgs_loop(loss, carry, stop_it, tol, memory, log, n_blocks=None):
    """The optax L-BFGS while_loop, shared by every loss flavor (XLA,
    Pallas single-target, Pallas multi-target).

    The single-target carry is ``(beta, state, gnorm, it, n_evals)``:
    ``n_evals`` is an int32 sum of objective evaluations (one scalar add
    an iteration, always on — a static switch would make two programs).
    ``gnorm`` is the gradient norm at the iterate the last body started
    from: the loop stops on the first iterate whose gradient (free: the
    last line search computed it) meets tol, with no line search from
    it, and ``it`` counts the updates made.
    A one-element carry ``(beta0,)`` is a fresh start (``_fresh_carry``),
    a four-element one a state without its counters: both are completed
    here, in the traced function.

    ``n_blocks`` switches on the stacked multi-solve semantics: the flat
    vector is ``n_blocks`` independent row blocks (classes, lam
    candidates, or both) sharing ONE iteration budget — every iteration
    advances every block, and the loop stops only when the MAX per-block
    gradient norm reaches tol ("every block converged"), matching the
    single-target criterion exactly. The carry then grows a
    ``(n_blocks,)`` int32 vector recording, per block, the last
    iteration at which that block's gradient norm still exceeded tol —
    the block's own convergence point INSIDE the joint trajectory.
    (Not identical to a standalone solve's ``n_iter_``: the shared
    L-BFGS curvature state and line search see every block at once, so
    per-block paths differ even though the separable optimum is the
    same.) Callers surface it as the per-candidate ``n_iter``.

    Each block's RETURNED iterate is frozen at its own convergence
    point — its first iterate whose gradient norm passed tol, exactly
    where a standalone solve of that block would have stopped. Blocks
    the budget cut off return the final joint iterate, again matching
    the standalone cap behavior. Without the freeze an early-converged
    candidate kept refining inside the joint program; the drift is
    below tol but was measured flipping razor-edge predictions, so the
    stacked C-grid's scores disagreed with per-candidate fits on tied
    candidates (the PR-1 tie-break parity failure).
    """
    opt = optax.lbfgs(memory_size=memory)
    value_and_grad = optax.value_and_grad_from_state(loss)
    track = n_blocks is not None

    def cond(carry):
        gnorm, it = carry[2], carry[3]
        return (it < stop_it) & (gnorm > tol)

    def body(carry):
        beta, state, _, it, n_evals = carry[:5]
        stored = state[-1].value     # what the last line search left
        value, grad = value_and_grad(beta, state=state)
        if track:
            conv, frozen, cmask = carry[5:]
            # the gradient is evaluated at the CURRENT iterate: a block
            # whose norm just passed tol converged AT this iterate —
            # record it before the update moves on
            norms = jnp.linalg.norm(grad.reshape(n_blocks, -1), axis=1)
            frozen = jnp.where(cmask[:, None], frozen,
                               beta.reshape(n_blocks, -1))
            cmask = cmask | (norms <= tol)
            gnorm = jnp.max(norms)
            conv = jnp.where(norms > tol, it + 1, conv)
        else:
            gnorm = jnp.linalg.norm(grad)

        def step(_):
            updates, new_state = opt.update(
                grad, state, beta, value=value, grad=grad, value_fn=loss
            )
            if log:  # static: the silent trace has no callback at all
                emit_jit_step(it, loss=value, grad_norm=gnorm)
            return (optax.apply_updates(beta, updates), new_state,
                    new_state[-1].info.num_linesearch_steps.astype(jnp.int32))

        # an iterate that already meets tol is the answer: no line search
        # from it (on the bf16 staircase one costs 1-20 evaluations that
        # move nothing), and no iteration counted; the loop ends on it
        done = gnorm <= tol
        beta, state, steps = jax.lax.cond(
            done, lambda _: (beta, state, jnp.zeros((), jnp.int32)), step,
            None)
        # objective evaluations so far: value_and_grad_from_state ran the
        # loss only where no finite value was stored (the first
        # iteration), the zoom line search once per step
        n_evals = n_evals + (~jnp.isfinite(stored)).astype(jnp.int32) + steps
        it = jnp.where(done, it, it + 1)
        if track:
            return beta, state, gnorm, it, n_evals, conv, frozen, cmask
        return beta, state, gnorm, it, n_evals

    if len(carry) == 1:
        carry = _fresh_carry(opt, carry[0])
    if len(carry) == 4:                  # a caller that starts from zero
        carry = (*carry, jnp.zeros((), jnp.int32))
    if track and len(carry) == 5:
        b0 = carry[0]
        carry = (*carry, jnp.zeros(n_blocks, jnp.int32),
                 b0.reshape(n_blocks, -1),
                 jnp.zeros(n_blocks, jnp.bool_))
    out = jax.lax.while_loop(cond, body, carry)
    if track:
        beta, state, gnorm, it, n_evals, conv, frozen, cmask = out
        merged = jnp.where(cmask[:, None], frozen,
                           beta.reshape(n_blocks, -1)).reshape(beta.shape)
        return merged, state, gnorm, it, n_evals, conv
    return out


def _per_block_iters(conv, it_total):
    """Per-block iteration counts in the single-target ``n_iter``
    convention: the updates a block took before its first iterate whose
    gradient norm passed tol (the tracker's count), clamped to the joint
    budget for blocks the cap cut off. Guarantees max(per_block) == the
    joint program's n_iter."""
    return np.minimum(np.asarray(conv, np.int64), int(it_total))


def _stacked_solve(chunk, *args, **kwargs):
    """One stacked L-BFGS program (``_lbfgs_loop`` with ``n_blocks``)
    from a fresh start, and what the host reads of it in one fetch:
    ``(beta, n_iter, grad_norm, conv, n_evals)`` as host values."""
    beta, _state, gnorm, it, n_evals, conv = chunk(*args, **kwargs)
    beta, gnorm, it, conv, n_evals = _fetch(beta, gnorm, it, conv, n_evals)
    return beta, int(it), float(gnorm), conv, int(n_evals)


@track_program("glm.lbfgs_multi_pallas")
@partial(jax.jit, static_argnames=("family", "reg", "memory", "log",
                                   "mesh", "interpret", "n_classes"))
def _lbfgs_multi_pallas_chunk(X, Y, mask, n_rows, carry, lam, pmask,
                              l1_ratio, stop_it, tol, family, reg, mesh,
                              n_classes, memory=10, log=False,
                              interpret=False):
    """Joint L-BFGS over the FLAT (C*d,) one-vs-rest vector whose data
    term comes from the multi-target Pallas kernel: every iteration
    reads X ONCE for all C classes (the stacked XLA path reads it twice
    — one batched forward matmul + one gradient matmul). The objective is
    separable across classes, so the joint optimum equals the per-class
    optima; the (d,) ``pmask`` is tiled to (C*d,) here. ``Y`` is the
    (C, n) one-hot target stack: the kernel takes class CODES, read off
    it here once a solve (padding rows are all-zero -> code 0, masked
    in-kernel)."""
    from ...ops.pallas_fused import fused_glm_multi_value_grad

    d = pmask.shape[0]
    pmask_t = jnp.tile(pmask, n_classes)
    codes = jnp.argmax(Y, axis=0).astype(jnp.float32)

    def data_vg(bflat):
        v, g = _shard_psum_call(
            mesh,
            lambda Bs, xs, cs, ms, nv: fused_glm_multi_value_grad(
                xs, nv, cs, Bs, family=family, interpret=interpret
            ),
            2, bflat.reshape(n_classes, d), X, codes, mask,
        )
        return v, g.reshape(-1)

    loss = _custom_vjp_loss(data_vg, n_rows, reg, lam, pmask_t, l1_ratio)
    return _lbfgs_loop(loss, carry, stop_it, tol, memory, log,
                       n_blocks=n_classes)


def lbfgs(X, y, mask, n_rows, beta0, family, reg, lam, pmask, l1_ratio=0.5,
          max_iter=100, tol=1e-6, memory=10, log=False, checkpoint_path=None,
          checkpoint_every=0, mesh=None, use_pallas=None,
          pallas_interpret=False, intercept=False, **_):
    """When ``checkpoint_path`` + ``checkpoint_every`` are set (via
    ``solver_kwargs``), the solve runs in k-iteration chunks with
    (beta, optimizer state, it) persisted after each — a killed 3-hour
    fit resumes mid-solve instead of from zero (VERDICT r2 #5)."""
    _check_smooth(reg, "lbfgs")
    use_pallas = _resolve_pallas(use_pallas, mesh, family, X)
    beta0 = _operand(beta0)
    run = partial(
        _lbfgs_chunk, X, y, mask, n_rows, lam=_operand(lam),
        pmask=_operand(pmask), l1_ratio=l1_ratio,
        tol=_operand(tol, beta0.dtype), family=family, reg=reg,
        memory=memory, log=log, use_pallas=use_pallas,
        mesh=mesh if use_pallas else None, interpret=pallas_interpret,
        intercept=intercept,
    )
    carry = (beta0,)          # a fresh start: the program builds the rest
    resumed_from = 0
    if not (checkpoint_path and checkpoint_every):
        _, result = run(carry=carry, stop_it=np.int32(max_iter))
        (result,) = _fetch(result)
        beta, (it, gnorm, n_evals) = result[:-3], result[-3:]
    else:
        import os

        from ...utils import checkpoint as ckpt

        it, gnorm = 0, np.inf
        if os.path.exists(os.path.abspath(checkpoint_path)):
            # the template of a saved carry, from its shapes alone (no
            # state is built to be thrown away), placed on one device
            opt = optax.lbfgs(memory_size=memory)
            one = jax.sharding.SingleDeviceSharding(jax.local_devices()[0])
            like = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one),
                jax.eval_shape(
                    lambda b: (*_fresh_carry(opt, b),
                               jnp.zeros((), jnp.int32)), beta0))
            restored = ckpt.restore_pytree(checkpoint_path, like=like)
            # host views: restored leaves come back committed to one
            # device; jit must be free to re-place them with X's sharding
            carry = tuple(jax.tree.map(
                lambda a: np.asarray(a), tuple(restored)
            ))
            it, gnorm = int(carry[3]), float(carry[2])
            resumed_from = it
        # a chunk whose start already meets tol returns it unmoved (and
        # it unchanged): the norm alone says the solve is done
        while it < max_iter and not gnorm <= tol:
            stop = min(it + int(checkpoint_every), max_iter)
            carry, result = run(carry=carry, stop_it=np.int32(stop))
            ckpt.save_pytree(checkpoint_path, tuple(carry))
            (result,) = _fetch(result)
            it, gnorm = int(result[-3]), float(result[-2])
        # completed: CLEAR the checkpoint — a finished solve's state left
        # on disk would be silently "resumed" (returning the stale beta)
        # by the next fit sharing the path. The path identifies ONE fit;
        # only a killed run leaves state behind.
        import shutil

        shutil.rmtree(os.path.abspath(checkpoint_path), ignore_errors=True)
        beta, n_evals = carry[0], carry[-1]
    # "fused": whether the Pallas kernel carried the data term — the
    # resident twin of the streamed fits' "fused_stream"; "n_evals": how
    # often the objective (one pass over X) ran, line search included
    info = {"n_iter": int(it), "grad_norm": float(gnorm),
            "n_evals": int(n_evals), "fused": bool(use_pallas)}
    if checkpoint_path and checkpoint_every:
        info["resumed_from"] = resumed_from
    return beta, info


# --------------------------------------------------------------------------
# Gradient descent with Armijo backtracking (dask_glm::gradient_descent)
# --------------------------------------------------------------------------

@track_program("glm.gradient_descent")
@partial(jax.jit, static_argnames=("family", "reg", "log", "use_pallas",
                                   "mesh", "interpret", "intercept"))
def _gd_run(X, y, mask, n_rows, beta0, lam, pmask, l1_ratio, max_iter, tol,
            init_step, family, reg, armijo=1e-4, backtrack=0.5, grow=2.0,
            log=False, use_pallas=False, mesh=None, interpret=False,
            intercept=False):
    loss = _select_loss(use_pallas, X, y, mask, n_rows, lam, pmask,
                        l1_ratio, family, reg, mesh, interpret, intercept)

    def outer_cond(carry):
        beta, step, gnorm, it = carry
        return (it < max_iter) & (gnorm > tol)

    def outer_body(carry):
        beta, step, _, it = carry
        val, grad = jax.value_and_grad(loss)(beta)
        g2 = jnp.sum(grad * grad)

        def ls_cond(t):
            return (loss(beta - t * grad) > val - armijo * t * g2) & (t > 1e-20)

        t = jax.lax.while_loop(ls_cond, lambda t: t * backtrack, step)
        beta = beta - t * grad
        if log:
            emit_jit_step(it, loss=val, grad_norm=jnp.sqrt(g2))
        return beta, t * grow, jnp.sqrt(g2), it + 1

    beta, step, gnorm, it = jax.lax.while_loop(
        outer_cond, outer_body,
        (beta0, jnp.asarray(init_step, beta0.dtype),
         jnp.asarray(jnp.inf, beta0.dtype), 0),
    )
    return beta, it, gnorm


def gradient_descent(X, y, mask, n_rows, beta0, family, reg, lam, pmask,
                     l1_ratio=0.5, max_iter=100, tol=1e-6, init_step=1.0,
                     log=False, mesh=None, use_pallas=None,
                     pallas_interpret=False, intercept=False, **_):
    _check_smooth(reg, "gradient_descent")
    use_pallas = _resolve_pallas(use_pallas, mesh, family, X)
    beta, it, gnorm = _gd_run(
        X, y, mask, n_rows, beta0, lam, pmask, l1_ratio,
        jnp.asarray(max_iter), jnp.asarray(tol, beta0.dtype),
        init_step, family, reg, log=log, use_pallas=use_pallas,
        mesh=mesh if use_pallas else None, interpret=pallas_interpret,
        intercept=intercept,
    )
    it, gnorm = _host_scalars(it, gnorm)
    return beta, {"n_iter": int(it), "grad_norm": float(gnorm),
                  "fused": bool(use_pallas)}


# --------------------------------------------------------------------------
# Proximal gradient with backtracking (dask_glm::proximal_grad) — handles
# non-smooth penalties via regularizers.prox
# --------------------------------------------------------------------------

@track_program("glm.proximal_grad")
@partial(jax.jit, static_argnames=("family", "reg", "log", "use_pallas",
                                   "mesh", "interpret", "intercept"))
def _pg_run(X, y, mask, n_rows, beta0, lam, pmask, l1_ratio, max_iter, tol,
            init_step, family, reg, backtrack=0.5, grow=1.2, log=False,
            use_pallas=False, mesh=None, interpret=False, intercept=False):
    # penalty handled by the prox: the selected loss is smooth-only
    smooth = _select_loss(use_pallas, X, y, mask, n_rows, lam * 0.0,
                          pmask, l1_ratio, family, "none", mesh, interpret,
                          intercept)

    def outer_cond(carry):
        beta, step, delta, it = carry
        return (it < max_iter) & (delta > tol)

    def outer_body(carry):
        beta, step, _, it = carry
        val, grad = jax.value_and_grad(smooth)(beta)

        def candidate(t):
            return regularizers.prox(reg, beta - t * grad, lam, t, pmask, l1_ratio)

        def ls_cond(t):
            z = candidate(t)
            dz = z - beta
            quad = val + jnp.vdot(grad, dz) + jnp.sum(dz * dz) / (2.0 * t)
            return (smooth(z) > quad) & (t > 1e-20)

        t = jax.lax.while_loop(ls_cond, lambda t: t * backtrack, step)
        z = candidate(t)
        delta = jnp.linalg.norm(z - beta) / jnp.maximum(t, 1e-20)
        if log:
            emit_jit_step(it, loss=val, opt_residual=delta)
        return z, t * grow, delta, it + 1

    beta, step, delta, it = jax.lax.while_loop(
        outer_cond, outer_body,
        (beta0, jnp.asarray(init_step, beta0.dtype),
         jnp.asarray(jnp.inf, beta0.dtype), 0),
    )
    return beta, it, delta


def proximal_grad(X, y, mask, n_rows, beta0, family, reg, lam, pmask,
                  l1_ratio=0.5, max_iter=100, tol=1e-7, init_step=1.0,
                  log=False, mesh=None, use_pallas=None,
                  pallas_interpret=False, intercept=False, **_):
    use_pallas = _resolve_pallas(use_pallas, mesh, family, X)
    beta, it, delta = _pg_run(
        X, y, mask, n_rows, beta0, lam, pmask, l1_ratio,
        jnp.asarray(max_iter), jnp.asarray(tol, beta0.dtype),
        init_step, family, reg, log=log, use_pallas=use_pallas,
        mesh=mesh if use_pallas else None, interpret=pallas_interpret,
        intercept=intercept,
    )
    it, delta = _host_scalars(it, delta)
    return beta, {"n_iter": int(it), "opt_residual": float(delta),
                  "fused": bool(use_pallas)}


# --------------------------------------------------------------------------
# Newton (dask_glm::newton) with step-halving safeguard, fully on device
# --------------------------------------------------------------------------

@track_program("glm.newton")
@partial(jax.jit, static_argnames=("family", "reg", "log", "use_pallas",
                                   "mesh", "interpret"))
def _newton_run(X, y, mask, n_rows, beta0, lam, pmask, l1_ratio, max_iter, tol,
                family, reg, log=False, use_pallas=False, mesh=None,
                interpret=False):
    fam = get_family(family)
    loss = _select_loss(use_pallas, X, y, mask, n_rows, lam, pmask,
                        l1_ratio, family, reg, mesh, interpret)
    d = beta0.shape[0]
    ridge = (lam * pmask if reg == "l2" else jnp.zeros_like(pmask)) + 1e-8

    if use_pallas:
        from ...ops.pallas_fused import fused_glm_value_grad_hess

        def vgh(beta):
            vs, gs, hs = _shard_psum_call(
                mesh,
                lambda bs, xs, ys, ms, nv: fused_glm_value_grad_hess(
                    xs, nv, ys, bs, family=family, interpret=interpret
                ),
                3, beta, X, y, mask,
            )
            pen, pen_g = jax.value_and_grad(
                lambda b: regularizers.value(reg, b, lam, pmask, l1_ratio)
            )(beta)
            return (vs / n_rows + pen, gs / n_rows + pen_g, hs / n_rows)

    def cond(carry):
        beta, gnorm, it = carry
        return (it < max_iter) & (gnorm > tol)

    def body(carry):
        beta, _, it = carry
        if use_pallas:
            # Newton's whole data touch in one X pass (eta + grad +
            # weighted Hessian statistics come from the fused kernel)
            val, grad, hess = vgh(beta)
            hess = hess + jnp.diag(ridge)
        else:
            val, grad = jax.value_and_grad(loss)(beta)
            eta = X @ beta
            w = fam.hess_weight(eta, y) * mask
            # (d, d) Hessian: per-shard X^T W X + ICI psum, replicated
            # solve
            hess = (X * w[:, None]).T @ X / n_rows + jnp.diag(ridge)
        # lstsq, not solve: stays finite on singular Hessians
        # (underdetermined n < d fits return the min-norm step)
        delta = jnp.linalg.lstsq(hess, grad)[0]

        def ls_cond(t):
            return (loss(beta - t * delta) > val) & (t > 1e-6)

        t = jax.lax.while_loop(ls_cond, lambda t: t * 0.5,
                               jnp.asarray(1.0, beta.dtype))
        beta = beta - t * delta
        if log:
            emit_jit_step(it, loss=val, grad_norm=jnp.linalg.norm(grad))
        return beta, jnp.linalg.norm(grad), it + 1

    beta, gnorm, it = jax.lax.while_loop(
        cond, body, (beta0, jnp.asarray(jnp.inf, beta0.dtype), 0)
    )
    return beta, it, gnorm


def newton(X, y, mask, n_rows, beta0, family, reg, lam, pmask, l1_ratio=0.5,
           max_iter=50, tol=1e-6, log=False, mesh=None, use_pallas=None,
           pallas_interpret=False, **_):
    _check_smooth(reg, "newton")
    pallas_auto = use_pallas is None
    use_pallas = _resolve_pallas(use_pallas, mesh, family, X)
    if use_pallas and pallas_auto:
        # Newton's kernel also carries a (d, d) accumulator — its VMEM
        # budget is tighter than the value+grad kernel's
        from ...ops.pallas_fused import glm_newton_tile

        use_pallas = glm_newton_tile(
            X.shape[0], X.shape[1], X.dtype.itemsize
        ) is not None

    beta, it, gnorm = _newton_run(
        X, y, mask, n_rows, beta0, lam, pmask, l1_ratio,
        jnp.asarray(max_iter), jnp.asarray(tol, beta0.dtype), family,
        reg, log=log, use_pallas=use_pallas,
        mesh=mesh if use_pallas else None, interpret=pallas_interpret,
    )
    it, gnorm = _host_scalars(it, gnorm)
    return beta, {"n_iter": int(it), "grad_norm": float(gnorm),
                  "fused": bool(use_pallas)}


# --------------------------------------------------------------------------
# Consensus ADMM (dask_glm::admm): per-shard local Newton solves inside
# shard_map, psum z-update. One ICI all-reduce per outer iteration where the
# reference pays a gather-to-client + broadcast over TCP.
# --------------------------------------------------------------------------

# rows of X one step of the blocked statistics touches at a time: the
# weighted copy ``x * w`` the Gram product reads is this large (32 MiB of
# f32 at any width), never X-sized
_NEWTON_BLOCK_BYTES = 32 * 1024 * 1024

# residual balancing (Boyd et al. 3.4.1): rho doubles where the primal
# residual exceeds ten times the dual one, halves in the opposite case
ADMM_BALANCE_RATIO = 10.0
ADMM_BALANCE_FACTOR = 2.0


def _newton_block_rows(n, d, itemsize=4):
    rows = max(_NEWTON_BLOCK_BYTES // (itemsize * max(d, 1)), 1024)
    return n if n <= rows else rows - rows % 1024


def _newton_stats(Xs, ys, ms, b, family, intercept):
    """A local Newton step's ONE touch of the data, blocked over rows:
    ``(X^T r, X^T W X)`` as SUMS over this shard's rows at ``b``, for the
    ``(d + 1,)`` unknowns ``[coef, intercept]`` when ``intercept`` (the
    intercept a scalar added to eta; its gradient entry Σ r and the
    Hessian's border ``X^T w`` / Σ w accumulated apart and joined once,
    after the loop: X keeps its width and no ones column exists), else
    for X's own columns. eta, the residual products and the border run at
    ``HIGHEST`` (f32 on the MXU, or the VPU's exact multiplies); the Gram
    ``X^T W X`` is a curvature estimate and runs at the backend's default
    (one bf16 pass on a TPU): its precision changes the path of the
    iterates, not their fixed point. Each block is a dynamic slice of X,
    so the only temporary is one block's weighted copy."""
    fam = get_family(family)
    n, d = Xs.shape
    hi = jax.lax.Precision.HIGHEST
    coef = b[:-1] if intercept else b
    rows = _newton_block_rows(n, d, Xs.dtype.itemsize)

    def block(Xb, yb, mb):
        eta = jnp.dot(Xb, coef, precision=hi)
        if intercept:
            eta = eta + b[-1]
        r = (fam.mean(eta) - yb) * mb
        w = fam.hess_weight(eta, yb) * mb
        # the Gram of ONE matrix, rows scaled by sqrt(w): whatever the
        # MXU rounds, the sum stays symmetric positive semi-definite
        xw = Xb * jnp.sqrt(w)[:, None]
        return (jnp.dot(r, Xb, precision=hi), jnp.sum(r),
                jnp.dot(xw.T, xw),
                jnp.dot(w, Xb, precision=hi), jnp.sum(w))

    def add(acc, new):
        return tuple(a + v for a, v in zip(acc, new))

    if rows == n:
        acc = block(Xs, ys, ms)
    else:
        def body(i, acc):
            cut = [jax.lax.dynamic_slice_in_dim(a, i * rows, rows)
                   for a in (Xs, ys, ms)]
            return add(acc, block(*cut))

        zero = jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype),
            jax.eval_shape(block, Xs[:rows], ys[:rows], ms[:rows]))
        acc = jax.lax.fori_loop(0, n // rows, body, zero)
        if n % rows:
            lo = n - n % rows
            acc = add(acc, block(Xs[lo:], ys[lo:], ms[lo:]))
    g, gb, H, hb, hbb = acc
    if not intercept:
        return g, H
    return (jnp.concatenate([g, gb[None]]),
            jnp.block([[H, hb[:, None]], [hb[None, :], hbb[None, None]]]))


def _label_sums(Xs, ys, ms, intercept):
    """``[X^T y, Σ y]`` over this shard's valid rows (``X^T y`` alone
    without ``intercept``), at ``HIGHEST``: the part of a Newton step's
    gradient that no step changes, taken once a solve for the kernel
    path, whose one pass over X reads no labels."""
    ym = ys * ms
    xty = jnp.dot(ym, Xs.astype(jnp.float32),
                  precision=jax.lax.Precision.HIGHEST)
    return jnp.concatenate([xty, jnp.sum(ym)[None]]) if intercept else xty


def _newton_stats_pallas(Xs, ms, b, xty, family, intercept, interpret):
    """``_newton_stats`` from ONE pass of the fused kernel
    (``ops/pallas_fused.fused_glm_newton_stats``): X streams through VMEM
    once a step, where the blocked XLA form reads every block for eta, for
    the residual products and for the Gram. ``xty``: ``_label_sums``."""
    from ...ops.pallas_fused import fused_glm_newton_stats

    nv = jnp.sum(ms.astype(jnp.int32))      # padding is trailing per shard
    s1, sp, H, hb, hbb = fused_glm_newton_stats(
        Xs, nv, b[:-1] if intercept else b, b[-1] if intercept else 0.0,
        family=family, interpret=interpret)
    if not intercept:
        return s1 - xty, H
    return (jnp.concatenate([s1, sp[None]]) - xty,
            jnp.block([[H, hb[:, None]], [hb[None, :], hbb[None, None]]]))


def _resolve_admm_pallas(use_pallas, mesh, family, X):
    """Auto gate of the fused Newton-statistics kernel for ADMM's local
    step: where ``_resolve_pallas`` would pick a kernel, and a shard's rows
    are whole row tiles of the kernel's VMEM budget (no padded copy of X is
    ever made for it)."""
    if use_pallas is not None:
        return bool(use_pallas)
    from ...ops.pallas_fused import glm_newton_tile

    if not _resolve_pallas(None, mesh, family, X):
        return False
    n_local = X.shape[0] // mesh.shape[DATA_AXIS]
    tile = glm_newton_tile(n_local, X.shape[1], X.dtype.itemsize)
    return tile is not None and n_local % tile == 0


@track_program("glm.admm")
@partial(jax.jit, static_argnames=("family", "reg", "local_iter", "mesh",
                                   "log", "intercept", "use_pallas",
                                   "interpret"))
def _admm_run(X, y, mask, n_rows, beta0, lam, pmask, l1_ratio, rho,
              max_iter, abstol, family, reg, local_iter, mesh,
              log=False, intercept=False, use_pallas=False,
              interpret=False):
    """The whole consensus solve as one program. Returns ONE f32 vector
    ``[*z, n_iter, local_steps, primal, dual, rho]``: what the host reads
    of a solve is one fetch of it (``admm``)."""
    n_shards = mesh.shape[DATA_AXIS]
    d1 = beta0.shape[0]
    eye = jnp.eye(d1, dtype=beta0.dtype)

    def shard_iter(Xs, ys, ms, xty, b, u, z, rho):
        b, u = b[0], u[0]
        v = z - u  # local target

        # local solve of  f_i(b) + rho/2 ||b - v||^2  by Newton steps:
        # at most ``local_iter``, ended after the step whose Newton
        # decrement g.H^-1.g fell to ``abstol**2`` (quadratic
        # convergence: the step after it would move b by far less, and
        # the local solve is exact to well under the outer stop)
        def newton_cond(c):
            _, k, dec = c
            return (k < local_iter) & (dec > abstol * abstol)

        def newton_step(c):
            b, k, _ = c
            if use_pallas:
                gs, hs = _newton_stats_pallas(Xs, ms, b, xty[0], family,
                                              intercept, interpret)
            else:
                gs, hs = _newton_stats(Xs, ys, ms, b, family, intercept)
            g = gs / n_rows + rho * (b - v)
            # rho > 0 makes h positive definite: a Cholesky solve (LU's
            # pivot bookkeeping is a loop of d + 1 tiny steps on its own)
            h = hs / n_rows + rho * eye
            delta = cho_solve((jnp.linalg.cholesky(h), True), g)
            return b - delta, k + 1, jnp.sum(g * delta)

        b, steps, _ = jax.lax.while_loop(
            newton_cond, newton_step,
            (b, jnp.zeros((), jnp.int32),
             jnp.asarray(jnp.inf, beta0.dtype)))
        bu_mean = jax.lax.pmean(b + u, DATA_AXIS)
        z_new = regularizers.prox(reg, bu_mean, lam, 1.0 / (rho * n_shards),
                                  pmask, l1_ratio)
        u = u + b - z_new
        primal = jax.lax.psum(jnp.sum((b - z_new) ** 2), DATA_AXIS)
        # the slowest block's count: what the outer iteration waited for
        steps = jax.lax.pmax(steps, DATA_AXIS)
        return b[None], u[None], z_new, primal, steps

    shard_iter_sm = jax.shard_map(
        shard_iter,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS, None), P(DATA_AXIS, None), P(DATA_AXIS, None),
                  P(), P()),
        out_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None), P(), P(), P()),
        check_vma=False,
    )
    # the label sums of the kernel path, once a solve (one row a shard; a
    # row of nothing where XLA's blocked statistics read the labels)
    xty = jax.shard_map(
        lambda Xs, ys, ms: _label_sums(Xs, ys, ms, intercept)[None],
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS, None), check_vma=False,
    )(X, y, mask) if use_pallas else jnp.zeros((n_shards, 0), beta0.dtype)

    def cond(carry):
        B, U, z, rho, it, steps, primal, dual = carry
        return (it < max_iter) & ((primal > abstol) | (dual > abstol))

    def body(carry):
        B, U, z, rho, it, steps, _, _ = carry
        B, U, z_new, primal2, k = shard_iter_sm(X, y, mask, xty, B, U, z,
                                                rho)
        dual = rho * jnp.sqrt(jnp.asarray(n_shards, z.dtype)) \
            * jnp.linalg.norm(z_new - z)
        primal = jnp.sqrt(primal2)
        # residual balancing; U is the scaled dual, rescaled on rho changes
        if log:
            emit_jit_step(it, primal_residual=primal, dual_residual=dual)
        grow = primal > ADMM_BALANCE_RATIO * dual
        shrink = dual > ADMM_BALANCE_RATIO * primal
        scale = jnp.where(
            grow, ADMM_BALANCE_FACTOR,
            jnp.where(shrink, 1.0 / ADMM_BALANCE_FACTOR, 1.0)).astype(z.dtype)
        return (B, U / scale, z_new, rho * scale, it + 1, steps + k, primal,
                dual)

    inf = jnp.asarray(jnp.inf, beta0.dtype)
    zero = jnp.zeros((), jnp.int32)
    B = jnp.broadcast_to(beta0[None], (n_shards, d1))
    _, _, z, rho, it, steps, primal, dual = jax.lax.while_loop(
        cond, body, (B, jnp.zeros_like(B), beta0, rho, zero, zero, inf, inf)
    )
    tail = jnp.stack([it.astype(z.dtype), steps.astype(z.dtype), primal,
                      dual, rho])
    return jnp.concatenate([z, tail])


def admm(X, y, mask, n_rows, beta0, family, reg, lam, pmask, l1_ratio=0.5,
         max_iter=250, tol=1e-4, rho=1.0, local_iter=8, mesh=None,
         log=False, intercept=False, use_pallas=None,
         pallas_interpret=False, **_):
    """Consensus ADMM over the mesh's row shards, one block a shard.

    Departures from ``dask_glm.algorithms.admm``, each deliberate: the
    local solves are Newton steps (upstream: scipy's L-BFGS-B to its own
    ``pgtol``) — at most ``local_iter`` an outer iteration, ended early
    once the Newton decrement is under ``tol**2`` (the local solve is
    then exact to well under the outer stop); ``rho`` is rebalanced
    (Boyd 3.4.1: x2 where primal > 10 dual, /2 where dual > 10 primal,
    the scaled dual rescaled with it); the stop is ``primal <= tol and
    dual <= tol`` with no relative term; the intercept is never
    penalised. ``intercept``: the last entry of beta
    is a scalar added to eta and X carries no ones column. A local step's
    touch of the data is ``solver_info_["local_step"]``: the fused kernel
    (``pallas_newton_stats``: one read of X a step) where
    ``_resolve_admm_pallas`` picks it, XLA's blocked loop
    (``xla_blocked``) elsewhere."""
    if reg == "none":
        reg, lam = "l2", 0.0
    beta0 = _operand(beta0)
    use_pallas = _resolve_admm_pallas(use_pallas, mesh, family, X)
    result = _admm_run(
        X, y, mask, n_rows, beta0, _operand(lam), _operand(pmask), l1_ratio,
        _operand(rho), np.int32(max_iter), _operand(tol), family, reg,
        int(local_iter), mesh, log=log, intercept=intercept,
        use_pallas=use_pallas, interpret=pallas_interpret,
    )
    (result,) = _fetch(result)
    z, (it, steps, primal, dual, rho_end) = result[:-5], result[-5:]
    return z, {"n_iter": int(it), "local_steps": int(steps),
               "primal_residual": float(primal),
               "dual_residual": float(dual), "rho": float(rho_end),
               "nnz": int(np.count_nonzero(z[:-1] if intercept else z)),
               "local_step": ("pallas_newton_stats" if use_pallas
                              else "xla_blocked"),
               "fused": bool(use_pallas)}


SOLVERS = {
    "admm": admm,
    "lbfgs": lbfgs,
    "newton": newton,
    "gradient_descent": gradient_descent,
    "proximal_grad": proximal_grad,
}

# solvers whose only touch of X is the loss from ``_select_loss``: they
# take ``intercept=True`` (the last entry of beta, X without a ones
# column) — and ADMM, whose local Newton step accumulates the intercept's
# gradient entry and the Hessian's border apart (``_newton_stats``).
# Newton's Hessian (``X.T W X``, the fused vgh kernel) indexes the
# intercept as a COLUMN of X.
SCALAR_INTERCEPT_SOLVERS = ("lbfgs", "gradient_descent", "proximal_grad",
                            "admm")


def solve(solver: str, **kwargs):
    if solver not in SOLVERS:
        raise ValueError(f"Unknown solver {solver!r}; options: {sorted(SOLVERS)}")
    if kwargs.get("intercept") and solver not in SCALAR_INTERCEPT_SOLVERS:
        raise ValueError(
            f"solver {solver!r} takes the intercept as a ones column of "
            f"X, not as intercept=True"
        )
    beta, info = SOLVERS[solver](**kwargs)
    return check_finite_result(beta, info, solver)


# smooth solvers whose whole solve is one jitted program — these vmap
# cleanly over stacked targets
_VMAP_SOLVERS = ("lbfgs",)


def solve_multi(solver, X, Y, mask, n_rows, B0, family, reg, lam, pmask,
                l1_ratio=0.5, max_iter=100, tol=1e-6, mesh=None, **kwargs):
    """Solve C independent GLMs sharing ONE design matrix (one-vs-rest
    multiclass): ``Y`` is (C, n) targets, ``B0`` (C, d) starts; returns
    ((C, d) betas, info).

    For L-BFGS the C solves run as a SINGLE stacked XLA program — the
    per-class matvecs batch into one (n,d)x(d,C) contraction on the MXU,
    the reference's closest analog being C separate dask-glm solves.
    Other solvers fall back to a per-class loop of their single-target
    programs (correct, C launches).

    Shared-iteration-budget semantics (stacked paths): the C blocks
    advance in lockstep inside one while_loop — every iteration updates
    EVERY class, and the loop runs until the slowest block's gradient
    norm reaches tol (or max_iter). A class that would have converged
    alone in fewer iterations keeps refining (harmless: its gradient is
    already below tol; the objective is separable so blocks cannot
    perturb each other). ``info["n_iter"]`` is therefore the budget the
    PROGRAM ran (the max), while ``info["n_iter_per_class"]`` records
    each block's own convergence point within that joint run — the
    last iteration its gradient norm still exceeded tol."""
    kwargs.pop("log", None)  # per-class step logs would interleave
    use_pallas = kwargs.pop("use_pallas", None)
    pallas_interpret = kwargs.pop("pallas_interpret", False)
    # leftover kwargs (e.g. checkpoint_path/checkpoint_every) are only
    # honored by the single-target solver functions — fall back to the
    # per-class loop rather than silently dropping them
    plain_kwargs = not {k for k in kwargs if k != "memory"}
    # fused multi-target path: logistic ONLY — the kernel rebuilds
    # one-vs-rest 0/1 targets from class codes, which would destroy
    # real-valued multi-output targets of other families
    if (solver == "lbfgs" and plain_kwargs and family == "logistic"
            and _resolve_pallas(use_pallas, mesh, family, None)):
        from ...ops.pallas_fused import glm_multi_tile

        C, d = B0.shape
        fits_vmem = glm_multi_tile(X.shape[0], d, C,
                                   X.dtype.itemsize) is not None
        if fits_vmem:
            _check_smooth(reg, solver)
            memory = int(kwargs.get("memory", 10))
            beta, it, gnorm, conv, n_evals = _stacked_solve(
                _lbfgs_multi_pallas_chunk, X, Y, mask, n_rows,
                (_operand(B0).reshape(-1),), _operand(lam),
                _operand(pmask), l1_ratio, np.int32(max_iter),
                _operand(tol), family, reg, mesh, C, memory=memory,
                interpret=pallas_interpret,
            )
            info = {"n_iter": it, "grad_norm": gnorm, "n_evals": n_evals,
                    "n_iter_per_class":
                        _per_block_iters(conv, it).tolist(),
                    "fused_multi": True}
            return check_finite_result(beta.reshape(C, d), info, solver)
        elif use_pallas is not None:
            raise ValueError(
                f"design too wide for the fused multi-target GLM kernel "
                f"(d={d}, C={C}) — explicit use_pallas=True cannot be "
                "honored; unset it for the stacked XLA path"
            )
    if solver in _VMAP_SOLVERS and plain_kwargs and not (
        use_pallas and solver == "lbfgs"
    ):
        # stacked joint solve over the flat (C*d,) vector — same
        # separable-objective argument as the Pallas multi chunk, with
        # an XLA data term: the C forward matvecs batch into ONE
        # (n,d)x(d,C) matmul. A jax.vmap of the single-target
        # while_loop solver was measured ~5-7x slower PER LANE on
        # XLA:CPU (batched-while_loop lowering) and is gone.
        _check_smooth(reg, solver)
        memory = int(kwargs.pop("memory", 10))
        C, d = B0.shape
        beta, it, gnorm, conv, n_evals = _stacked_solve(
            _multi_stacked_chunk, X, Y, mask, n_rows,
            (_operand(B0).reshape(-1),), _operand(lam), _operand(pmask),
            l1_ratio, np.int32(max_iter), _operand(tol), family, reg, C,
            memory=memory,
        )
        info = {"n_iter": it, "grad_norm": gnorm, "n_evals": n_evals,
                "n_iter_per_class": _per_block_iters(conv, it).tolist()}
        return check_finite_result(beta.reshape(C, d), info, solver)
    # per-class loop: forward the pallas knobs — the single-target
    # solvers honor them (an explicit use_pallas request must not be
    # silently dropped here)
    if use_pallas is not None:
        kwargs["use_pallas"] = use_pallas
    if pallas_interpret:
        kwargs["pallas_interpret"] = pallas_interpret
    betas, iters = [], []
    for c in range(Y.shape[0]):
        beta_c, info_c = solve(
            solver, X=X, y=Y[c], mask=mask, n_rows=n_rows, beta0=B0[c],
            family=family, reg=reg, lam=lam, pmask=pmask,
            l1_ratio=l1_ratio, max_iter=max_iter, tol=tol, mesh=mesh,
            **kwargs,
        )
        betas.append(np.asarray(beta_c))
        iters.append(info_c.get("n_iter") or 0)
    return np.stack(betas), {"n_iter": int(max(iters)),
                             "n_iter_per_class": [int(i) for i in iters]}


def _multi_stacked_body(X, Y, mask, n_rows, carry, lam, pmask, l1_ratio,
                        stop_it, tol, family, reg, C, memory=10):
    """Joint L-BFGS over the FLAT (C*d,) multi-target vector with an XLA
    data term: one (n,d)x(d,C) matmul serves every target's forward pass
    and one (d,n)x(n,C) their gradients. ``Y`` is (C, n) targets sharing
    one ``lam``; separable objective, so the joint optimum equals the
    per-target optima."""
    d = X.shape[1]

    def loss(bflat):
        B = bflat.reshape(C, d)
        eta = jax.lax.dot_general(
            X, B.astype(X.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                       # (n, C)
        pw = get_family(family).pointwise(eta, Y.T)
        base = jnp.sum(pw * mask[:, None]) / n_rows
        return base + regularizers.value(
            reg, bflat, lam, jnp.tile(pmask, C), l1_ratio
        )

    # stop when EVERY class block has converged to tol (max per-block
    # norm) — identical criterion to the per-class solves
    return _lbfgs_loop(loss, carry, stop_it, tol, memory, False,
                       n_blocks=C)


def _lam_grid_body(X, y, mask, fold_id, n_train, carry, lams, pmask,
                   stop_it, tol, family, reg, k, n_folds, intercept,
                   memory=10):
    """Joint L-BFGS over ``n_folds * k`` stacked blocks, block
    ``j = f * k + c`` the model of candidate ``c`` trained on fold ``f``'s
    training rows: one ``(m, d) x (d, n)`` product serves every block's
    forward pass and one ``(m, n) x (n, d)`` their gradients, over the ONE
    resident design. ``fold_id`` (``(n,)`` int32, or None for one fold of
    every row) says which rows count: row i trains every block whose fold
    is not ``fold_id[i]``; ``n_train`` (``(n_folds,)`` f32) is each fold's
    training-row count, the mean's divisor, and ``lams`` already holds
    each block's ``1 / (C * n_train)``. ``intercept`` (static): the last
    entry of each block is added to eta in f32 (``_smooth_loss``'s
    contract), X is as wide as its features. The objective is separable
    across blocks, so the joint optimum is every block's own optimum, and
    each block converges on its own gradient norm (``_lbfgs_loop``)."""
    d = X.shape[1]
    m = n_folds * k
    fold = jnp.arange(m) // k                       # block j's fold
    inv_train = (1.0 / n_train)[fold]               # (m,)
    def loss(bflat):
        B = bflat.reshape(m, -1)
        eta = jax.lax.dot_general(
            B[:, :d].astype(X.dtype), X, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                        # (m, n)
        if intercept:
            eta = eta + B[:, d:]
        pw = get_family(family).pointwise(eta, y[None, :])
        # each block's row weights as an expression of the fold ids, never
        # an (m, n) array of their own: XLA fuses them into the reduction
        # (and into the cotangent the gradient product reads)
        weight = mask[None, :] if fold_id is None else jnp.where(
            fold_id[None, :] == fold[:, None], 0.0, mask[None, :])
        base = jnp.sum(jnp.sum(pw * weight, axis=1) * inv_train)
        if reg == "none":
            return base
        bp = B * pmask[None, :]
        return base + 0.5 * jnp.sum(lams * jnp.sum(bp * bp, axis=1))

    # stop when EVERY block has converged to tol (max per-block norm) —
    # identical criterion to per-candidate solves
    return _lbfgs_loop(loss, carry, stop_it, tol, memory, False,
                       n_blocks=m)


def _lam_grid_multi_body(X, Y, mask, n_rows, carry, lams, pmask, stop_it,
                         tol, family, reg, k, C, memory=10):
    """C-grid x one-vs-rest: k candidates x C classes as ONE stacked
    (k*C*d,) joint solve. ``Y`` is (C, n) one-hot targets shared by all
    candidates; block j = i*C + c solves class c at lam_i. One
    (n,d)x(d,k*C) matmul per iteration serves the whole search fold."""
    d = X.shape[1]

    def loss(bflat):
        B = bflat.reshape(k * C, d)
        eta = jax.lax.dot_general(
            X, B.astype(X.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                     # (n, k*C)
        targets = jnp.tile(Y.T, (1, k))                       # (n, k*C)
        pw = get_family(family).pointwise(eta, targets)
        base = jnp.sum(pw * mask[:, None]) / n_rows
        if reg == "none":
            return base
        bp = B * pmask[None, :]
        lam_rep = jnp.repeat(lams, C)                         # (k*C,)
        return base + 0.5 * jnp.sum(lam_rep * jnp.sum(bp * bp, axis=1))

    return _lbfgs_loop(loss, carry, stop_it, tol, memory, False,
                       n_blocks=k * C)


# The stacked C-grid / OvR direct-solve programs build through the plan
# layer (ISSUE 15): identical jit flags and bodies (jaxprs byte-
# identical to the decorator-built programs — asserted in
# tests/test_plans.py), with cache keying / track_program registration
# owned by plans.ProgramPlan instead of this call site. Module-level builds, so XLA's compile cache is shared
# across estimator instances exactly as before.
_multi_stacked_chunk = ProgramPlan(
    name="glm.lbfgs_multi", body=_multi_stacked_body,
    static_argnames=("family", "reg", "C", "memory"),
    group="stacked-solve",
).build()

_lam_grid_chunk = ProgramPlan(
    name="glm.lbfgs_lam_grid", body=_lam_grid_body,
    static_argnames=("family", "reg", "k", "n_folds", "intercept",
                     "memory"),
    group="stacked-solve",
).build()

_lam_grid_multi_chunk = ProgramPlan(
    name="glm.lbfgs_lam_grid_multi", body=_lam_grid_multi_body,
    static_argnames=("family", "reg", "k", "C", "memory"),
    group="stacked-solve",
).build()


def solve_lam_grid_multi(X, Y, mask, n_rows, lams, pmask, family, reg,
                         max_iter=100, tol=1e-6, memory=10):
    """Multiclass variant of :func:`solve_lam_grid`: returns
    ((k, C, d) betas, info) for k lam values over the shared (C, n)
    one-vs-rest targets."""
    _check_smooth(reg, "lbfgs")
    lams = _operand(lams)
    k = int(lams.shape[0])
    C = int(Y.shape[0])
    d = X.shape[1]
    beta, it, gnorm, conv, n_evals = _stacked_solve(
        _lam_grid_multi_chunk, X, Y, mask, n_rows,
        (np.zeros((k * C * d,), np.float32),), lams, _operand(pmask),
        np.int32(max_iter), _operand(tol), family, reg, k, C,
        memory=memory,
    )
    # block j = i*C + c: a candidate's own n_iter is its slowest class
    # (the iteration count a standalone OvR fit of that candidate would
    # have reported)
    conv_kc = _per_block_iters(conv, it).reshape(k, C)
    info = {"n_iter": it, "grad_norm": gnorm, "n_evals": n_evals,
            "lam_grid": k, "n_classes": C,
            "n_iter_per_candidate": conv_kc.max(axis=1).tolist(),
            "n_iter_per_block": conv_kc.tolist()}
    return check_finite_result(beta.reshape(k, C, d), info, "lbfgs")


def solve_lam_grid(X, y, mask, n_rows, lams, pmask, family, reg,
                   max_iter=100, tol=1e-6, memory=10, fold_id=None,
                   n_train=None, intercept=False):
    """GLM solves differing ONLY in the l2 strength and in which rows they
    train on, as ONE compiled program sharing the design matrix — a whole
    C grid over every cross-validation fold costs one X pass per
    iteration instead of one per candidate and fold (SURVEY.md §3.4
    'combos batched when homogeneous'; the reference's analog is a
    dask-glm solve per candidate per fold). ``lams`` is ``n_folds * k``
    long, fold-major (block ``f * k + c``); ``fold_id`` / ``n_train`` as
    ``_lam_grid_body`` takes them (None / ``[n_rows]``: one fold of every
    row, a plain C grid). Returns ``((n_folds * k, d [+ 1]) betas,
    info)``; raises on non-finite results (callers fall back to
    per-candidate fits where error_score= applies individually).

    The blocks share one iteration budget (see :func:`solve_multi`):
    ``info["n_iter"]`` is the joint program's iteration count (the
    slowest block's), ``info["n_iter_per_candidate"]`` each block's own
    convergence point within the joint trajectory — the last iteration
    its gradient norm still exceeded tol — and ``info["n_evals"]`` how
    often the stacked objective (one pass over X) ran."""
    _check_smooth(reg, "lbfgs")
    lams = _operand(lams)
    m = int(lams.shape[0])
    n_train = np.asarray([n_rows] if n_train is None else n_train,
                         np.float32)
    n_folds = int(n_train.shape[0])
    k = m // n_folds
    p = X.shape[1] + int(intercept)
    beta, it, gnorm, conv, n_evals = _stacked_solve(
        _lam_grid_chunk, X, y, mask, fold_id, n_train,
        (np.zeros((m * p,), np.float32),), lams, _operand(pmask),
        np.int32(max_iter), _operand(tol), family, reg, k, n_folds,
        bool(intercept), memory=memory,
    )
    info = {"n_iter": it, "grad_norm": gnorm, "n_evals": n_evals,
            "lam_grid": k, "n_folds": n_folds,
            "n_iter_per_candidate": _per_block_iters(conv, it).tolist()}
    return check_finite_result(beta.reshape(m, p), info, "lbfgs")
