#!/usr/bin/env bash
# Repo verify: lint + the ROADMAP.md tier-1 test command, verbatim.
#
#   scripts/verify.sh          # lint, then the full tier-1 suite
#   scripts/verify.sh --lint   # lint only (fast pre-commit gate)

cd "$(dirname "$0")/.." || exit 1

# -- lint: every shard_map site states its replication semantics ------------
# Under the installed jax (0.9) a shard_map body that autodiffs a
# REPLICATED input already receives the cross-shard sum when
# check_vma=True (the transpose of the implicit pvary is a psum); a body
# that then psums it itself returns a gradient D times too large. Every
# body in this package does its own cross-shard accounting, so every call
# is `jax.shard_map(..., check_vma=False)`, spelled out at the site, and
# nothing imports shard_map from anywhere else.
bad=$(python - <<'PY'
import pathlib, re
for p in sorted(pathlib.Path("dask_ml_tpu").rglob("*.py")):
    s = p.read_text()
    for m in re.finditer(r"(?<![\w.`])(?:jax\.)?shard_map\(", s):
        depth, i = 0, m.end() - 1
        while True:
            depth += {"(": 1, ")": -1}.get(s[i], 0)
            if depth == 0:
                break
            i += 1
        call = s[m.start():i + 1]
        line = s.count("\n", 0, m.start()) + 1
        if not call.startswith("jax.shard_map("):
            print(f"{p}:{line}: call jax.shard_map directly")
        elif not re.search(r"check_vma=(True|False)\b", call):
            print(f"{p}:{line}: shard_map call does not state check_vma")
    for m in re.finditer(r"^\s*(from|import) .*shard_map", s, re.M):
        print(f"{p}:{s.count(chr(10), 0, m.start()) + 1}: import of shard_map")
PY
)
if [ -n "$bad" ]; then
    echo "LINT FAIL: shard_map sites must be jax.shard_map(..., check_vma=...):"
    echo "$bad"
    exit 1
fi
echo "lint OK: every shard_map site is jax.shard_map with check_vma stated"

# -- lint: the serving package must never import from tests/ -----------------
# (a production subsystem reaching into test fixtures would make the
# test tree a runtime dependency)
bad=$(grep -rn --include='*.py' -E '^[[:space:]]*(from[[:space:]]+tests|import[[:space:]]+tests)\b' \
      dask_ml_tpu/serving 2>/dev/null)
if [ -n "$bad" ]; then
    echo "LINT FAIL: dask_ml_tpu/serving must not import from tests/:"
    echo "$bad"
    exit 1
fi
echo "lint OK: serving package imports nothing from tests/"

# -- lint: every public config knob must be documented in README -------------
# (the config table is the operator's contract; a knob that ships
# undocumented is how obs_programs' extra-AOT-compile surprise happened)
knobs=$(grep -E '^    [a-z][a-z0-9_]*: ' dask_ml_tpu/config.py \
        | sed -E 's/^ +([a-z0-9_]+):.*/\1/')
missing=""
for k in $knobs; do
    if ! grep -q "$k" README.md; then
        missing="$missing $k"
    fi
done
if [ -n "$missing" ]; then
    echo "LINT FAIL: config knobs missing from the README config table:"
    echo "   $missing"
    exit 1
fi
echo "lint OK: every config.py knob is documented in README.md"

if [ "${1:-}" = "--lint" ]; then
    exit 0
fi

# -- perf smoke: super-block dispatch collapse (ISSUE 3) ---------------------
# streamed-SGD at smoke scale: fails when dispatches_per_pass exceeds
# ceil(n_blocks / superblock_k) + 1 or when passes after the first pay
# any new XLA compiles — the regressions throughput numbers hide.
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu python scripts/perf_smoke.py; then
    echo "VERIFY FAIL: super-block perf smoke"
    exit 1
fi

# -- live-scrape gate (ISSUE 5): a subprocess streamed fit with
# obs_http_port set must answer /healthz 200 and expose >=1 histogram
# series + >=1 fit progress gauge on /metrics WHILE it runs.
if ! timeout -k 10 300 python scripts/live_smoke.py; then
    echo "VERIFY FAIL: live telemetry scrape gate"
    exit 1
fi

# -- multichip dryrun (8 virtual CPU devices): the sharded lbfgs/ADMM
# paths must run AND record a flight-recorder trace the report CLI can
# render (spans + programs tables) — asserted inside the script.
if ! timeout -k 10 300 python scripts/multichip_dryrun.py; then
    echo "VERIFY FAIL: multichip dryrun (sharded paths + recorded trace)"
    exit 1
fi

# -- fleet gate (ISSUE 6): a subprocess 2-replica fleet under ragged
# traffic with one hot-swap mid-run must pay zero post-warmup compiles,
# lose no request across the swap, and show per-replica stats on /status.
if ! timeout -k 10 300 python scripts/fleet_smoke.py; then
    echo "VERIFY FAIL: serving fleet gate (hot-swap / replicas / status)"
    exit 1
fi

# -- drift gate (ISSUE 7): a subprocess fit + serve with an injected
# mean-shifted request stream must push drift_score over threshold and
# increment drift_alerts_total while an in-distribution control stream
# stays below; a mid-run hot swap must publish canary series for both
# versions — all with zero post-warmup compiles.
if ! timeout -k 10 300 python scripts/drift_smoke.py; then
    echo "VERIFY FAIL: drift gate (quality observability)"
    exit 1
fi

# -- chaos gate (ISSUE 11): a subprocess streamed fit SIGKILLed mid-pass
# must auto-resume to 1e-6 parity; an injected staging IOError must be
# retried (counters visible on /metrics) with a bit-identical result;
# a replica killed under ragged traffic must be supervisor-rebuilt with
# zero lost requests and zero post-rewarm XLA compiles.
if ! timeout -k 10 500 python scripts/chaos_smoke.py; then
    echo "VERIFY FAIL: chaos gate (fault injection / resume / supervision)"
    exit 1
fi

# -- federation gate (ISSUE 17): TWO subprocess fleet processes behind
# one router; SIGKILL the currently-preferred process mid-traffic — zero
# lost admitted requests (survivor traces carry rerouted_from_process),
# the next publish re-converges the survivor to the control registry's
# version with zero post-warmup compiles; a replayed burst must fire a
# plans-warm autoscale scale-up while holding its SLO verdict.
if ! timeout -k 10 500 python scripts/federation_smoke.py; then
    echo "VERIFY FAIL: federation gate (routing / failover / autoscale)"
    exit 1
fi

# -- incident gate (ISSUE 20): a subprocess fleet with an injected
# fault_plan SLO breach must close detect -> snapshot -> artifact:
# /alerts transitions firing -> resolved, EXACTLY ONE rate-limited
# incident bundle lands (open spans + counter/histogram snapshots +
# programs table), zero post-warmup XLA compiles, POST /profile answers
# the off-TPU no-op-with-reason, and a SIGKILL mid-capture-loop never
# publishes a truncated bundle (the save_host atomic-publish contract).
if ! timeout -k 10 500 python scripts/incident_smoke.py; then
    echo "VERIFY FAIL: incident gate (alerts / capture / profiling)"
    exit 1
fi

# -- serving suite (fast, targeted): the online-inference subsystem gates
# the same as lint — a broken server should fail verify in ~1min, before
# the full tier-1 wait. timeout-wrapped like tier-1: a hung serving
# worker must not block verify forever.
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
      tests/test_serving.py tests/test_fleet.py -q -p no:cacheprovider \
      -p no:xdist -p no:randomly; then
    echo "VERIFY FAIL: serving tests"
    exit 1
fi

# -- tier-1 (ROADMAP.md, verbatim) -------------------------------------------
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); exit $rc
