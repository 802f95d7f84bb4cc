"""ProgramPlan: the one build path for every compiled specialization.

Every jitted hot path in this repo used to hand-assemble the same
things at its own call site: ``jax.jit`` flags (donation, statics),
``track_program`` registration, and some ad-hoc warmup bookkeeping. A
:class:`ProgramPlan` is the declarative spec — callable body, donation slots, static axes, a cache
key carrying everything the traced program's identity depends on (mesh,
dtype/mxu, parameter shapes, ladder rung), a program name and a ladder
reference — and :meth:`ProgramPlan.build` is the ONE path that turns it
into a tracked jitted entry point:

1. the process-wide build cache is consulted (``config.plan_cache``):
   two builds of an identical spec return the SAME tracked callable,
   so the second client's warmup hits warm jit caches instead of
   re-tracing — counted as ``plan_cache_hits``;
2. on a miss the body is jitted with exactly the declared donation /
   static flags and wrapped in ``track_program`` — the jaxpr is
   byte-identical to a hand-assembled
   ``track_program(name)(jax.jit(body, ...))`` because it IS that
   call — and the plan registers in the attribution registry so the
   report CLI / ``/status`` can name the plan (and ladder rung) that
   minted any specialization.

Pre-jitted program builders (the super-block scan flavors, which carry
their own ``lru_cache`` build caches keyed on mesh/dtype/fusion) route
through :func:`tracked` instead: same ``track_program`` wrapper, same
attribution registry, scan bodies untouched.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading

__all__ = ["ProgramPlan", "tracked", "register_attr", "note_rung",
           "annotate_programs", "plans_snapshot", "plans_reset"]

_lock = threading.Lock()
# insertion-ordered build cache with a hard cap: a long-running process
# churning through many differently-shaped models must not pin every
# historical entry point (and its compiled executables) forever — past
# the cap the OLDEST spec is evicted (an evicted fn stays alive wherever
# a CompiledBatchFn still holds it; an identical later build just pays
# its compiles again)
_BUILD_CACHE: dict = {}
_BUILD_CACHE_MAX = 256
_tokens = itertools.count(1)

# attribution registry: program name -> {group, ladder, rungs, mesh}
# (which plan family owns a program, which shape ladder feeds it, which
# rungs have minted specializations so far, and — for the sharded
# super-block flavors — the "DxM" mesh the program runs over)
_ATTR: dict = {}


def register_attr(name: str, group: str = "plan",
                  ladder: str | None = None,
                  mesh: str | None = None) -> None:
    with _lock:
        e = _ATTR.get(name)
        if e is None:
            _ATTR[name] = {"group": group, "ladder": ladder,
                           "mesh": mesh, "rungs": set()}
        else:
            if group:
                e["group"] = group
            if ladder:
                e["ladder"] = ladder
            if mesh:
                e["mesh"] = mesh


def note_rung(name: str, rung) -> None:
    """Record that ``rung`` of ``name``'s ladder minted (or warmed) a
    specialization — the report CLI's ladder:rung attribution."""
    if name is None or rung is None:
        return
    with _lock:
        e = _ATTR.setdefault(name, {"group": "plan", "ladder": None,
                                    "rungs": set()})
        e["rungs"].add(int(rung))


def _ladder_rung_str(e: dict) -> str | None:
    if not e.get("ladder"):
        return None
    rungs = sorted(e.get("rungs") or ())
    if rungs:
        return f"{e['ladder']}:{','.join(str(r) for r in rungs)}"
    return str(e["ladder"])


def annotate_programs(rows) -> None:
    """Stamp plan attribution onto program-registry snapshot rows (the
    ``plan`` column the report CLI renders): the owning plan group, and
    ``ladder:rung`` when a shape ladder fed the program."""
    with _lock:
        attr = {k: dict(v, rungs=set(v["rungs"])) for k, v in
                _ATTR.items()}
    for row in rows:
        e = attr.get(row.get("program"))
        if e is None:
            continue
        row["plan"] = e["group"]
        lr = _ladder_rung_str(e)
        if lr:
            row["ladder_rung"] = lr
        if e.get("mesh"):
            # sharded super-block programs carry the "DxM" mesh shape
            # they were built over (ISSUE 18) — the programs-table
            # mesh column
            row["mesh"] = e["mesh"]


def plans_snapshot() -> list:
    """One row per planned program: plan group, ladder, the rungs that
    minted specializations, and the warmup/cache-hit counts — the
    ``plans`` table on ``/status`` and in the report CLI."""
    from .warmup import warmups

    stats = warmups.stats_by_program()
    with _lock:
        names = sorted(_ATTR)
        attr = {k: dict(_ATTR[k], rungs=sorted(_ATTR[k]["rungs"]))
                for k in names}
    rows = []
    for name in names:
        e = attr[name]
        st = stats.get(name, {})
        rows.append({
            "program": name,
            "plan": e["group"],
            "ladder": e.get("ladder") or "-",
            "rungs": ",".join(str(r) for r in e["rungs"]) or "-",
            "warmups": int(st.get("warmups", 0)),
            "warm_hits": int(st.get("hits", 0)),
        })
    return rows


def plans_reset() -> None:
    from .warmup import warmups

    with _lock:
        _ATTR.clear()
        _BUILD_CACHE.clear()
    warmups.reset()


@dataclasses.dataclass
class ProgramPlan:
    """Declarative spec of one compiled program (see module docstring).

    ``key`` must carry everything the traced program's identity depends
    on beyond the body itself — parameter-shape signatures, mesh,
    dtype/mxu, ladder rung — because the build cache treats two plans
    with equal (name, key, donate, statics) as the same program. With
    ``key=None`` the body object itself keys the cache (right for
    module-level bodies, useless for per-call closures — pass an
    explicit key there).
    """

    name: str
    body: object
    donate: tuple = ()
    static_argnums: tuple = ()
    static_argnames: tuple = ()
    key: object = None
    ladder: str | None = None
    group: str = "plan"
    # "DxM" for sharded super-block programs (rendered by the report
    # CLI's programs table); None for mesh-free programs
    mesh: str | None = None

    def cache_key(self):
        key = self.key if self.key is not None else self.body
        try:
            return hash((self.name, key, tuple(self.donate),
                         tuple(self.static_argnums),
                         tuple(self.static_argnames))), \
                (self.name, key, tuple(self.donate),
                 tuple(self.static_argnums),
                 tuple(self.static_argnames))
        except TypeError:
            return None

    def build(self):
        """The tracked jitted entry point for this plan — see the
        module docstring for the one-path contract."""
        from ..config import get_config

        ck = self.cache_key()
        use_cache = bool(get_config().plan_cache) and ck is not None
        if use_cache:
            with _lock:
                hit = _BUILD_CACHE.get(ck[1])
            if hit is not None:
                from ..observability._counters import record_plan_build

                record_plan_build(cached=True)
                return hit
        import jax

        from ..observability import track_program
        from ..observability._counters import record_plan_build

        kw = {}
        if self.donate:
            kw["donate_argnums"] = tuple(self.donate)
        if self.static_argnums:
            kw["static_argnums"] = tuple(self.static_argnums)
        if self.static_argnames:
            kw["static_argnames"] = tuple(self.static_argnames)
        fn = track_program(self.name)(jax.jit(self.body, **kw))
        fn.plan_token = next(_tokens)
        fn.plan_name = self.name
        register_attr(self.name, group=self.group, ladder=self.ladder,
                      mesh=self.mesh)
        record_plan_build(cached=False)
        if use_cache:
            with _lock:
                _BUILD_CACHE.setdefault(ck[1], fn)
                while len(_BUILD_CACHE) > _BUILD_CACHE_MAX:
                    _BUILD_CACHE.pop(next(iter(_BUILD_CACHE)))
        return fn


def tracked(name, fn=None, *, group="superblock", ladder=None,
            mesh=None):
    """Route a pre-jitted program through the plan layer: registers the
    plan attribution and applies the SAME ``track_program`` wrapper a
    :class:`ProgramPlan` build would — the scan body and its jit flags
    stay exactly the caller's, so the jaxpr is untouched. Usable as a
    decorator (``@tracked("name")``) or a call (``tracked(name, run)``).
    ``mesh`` ("DxM") tags sharded programs for the report CLI.
    """
    if fn is None:
        return lambda f: tracked(name, f, group=group, ladder=ladder,
                                 mesh=mesh)
    from ..observability import track_program

    register_attr(name, group=group, ladder=ladder, mesh=mesh)
    out = track_program(name)(fn)
    out.plan_token = next(_tokens)
    out.plan_name = name
    return out
