"""8-device CPU multichip dryrun with a recorded flight-recorder trace.

On 8 virtual XLA:CPU devices
(``--xla_force_host_platform_device_count``) this goes beyond "does the
sharded path run": the run records a span trace + program registry under
``config.trace_dir`` (spans) plus a separate counters/programs file and
ASSERTS ``report --merge`` folds both into ONE timeline rendering spans
AND a programs table for the sharded L-BFGS and ADMM fit paths — the
observability the next wedged-TPU round will need, proven on the same
virtual mesh the tier-1 suite uses.

Prints one JSON line:

    {"n_devices": 8, "ok": true, "rc": 0, "trace_records": ...,
     "report_spans": [...], "report_programs": [...]}

Run: ``python scripts/multichip_dryrun.py``.
"""

import json
import os
import sys
import tempfile
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# 8 virtual devices BEFORE jax initializes; never downgrade an explicit
# operator setting
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

N_DEVICES = 8


def main():
    out = {"n_devices": None, "rc": 0, "ok": False, "skipped": False,
           "tail": ""}
    trace_dir = tempfile.mkdtemp(prefix="multichip_trace_")
    try:
        import jax
        import numpy as np

        out["n_devices"] = len(jax.devices())
        if out["n_devices"] < N_DEVICES:
            raise RuntimeError(
                f"expected {N_DEVICES} virtual devices, got "
                f"{out['n_devices']} (XLA_FLAGS not honored?)"
            )
        from dask_ml_tpu import config
        from dask_ml_tpu import observability as obs
        from dask_ml_tpu.linear_model import LogisticRegression
        from dask_ml_tpu.observability.report import (build_report,
                                                      load_records,
                                                      report_data)
        from dask_ml_tpu.parallel import as_sharded

        rng = np.random.RandomState(0)
        n, d = 16_384, 32
        X = rng.randn(n, d).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        Xs, ys = as_sharded(X), as_sharded(y)
        obs.programs_reset()
        with config.set(trace_dir=trace_dir, obs_programs=True):
            # the two sharded solve flavors: one-program L-BFGS
            # (per-shard matmuls + psum) and shard_map consensus ADMM
            lb = LogisticRegression(solver="lbfgs", max_iter=20).fit(Xs, ys)
            ad = LogisticRegression(solver="admm", max_iter=20).fit(Xs, ys)
            assert lb.score(Xs, ys) > 0.6 and ad.score(Xs, ys) > 0.6
            # sharded STREAMED fits (ISSUE 9): host data, super-blocks
            # batch-sharded over the 8-device mesh, psum-bearing
            # shard_map scan programs — SGD (per-step gradient psum)
            # and the streamed GLM vg reducer (one psum per super-block)
            from dask_ml_tpu.models.sgd import SGDClassifier

            with config.set(stream_block_rows=n // 8,
                            trace_dir=trace_dir, obs_programs=True):
                ssgd = SGDClassifier(max_iter=2, random_state=0,
                                     shuffle=False).fit(X, y)
                sglm = LogisticRegression(solver="lbfgs",
                                          max_iter=10).fit(X, y)
            sgd_st = dict(getattr(ssgd, "_last_stream_stats", None)
                          or {})
            assert sgd_st.get("sb_shards") == 8, sgd_st
            assert ssgd.score(X, y) > 0.6
            assert sglm.solver_info_.get("stream_shards") == 8, \
                sglm.solver_info_
            trace = os.path.join(trace_dir, "trace.jsonl")
            # counters/programs land in a SEPARATE file, the shape a
            # multi-process run produces (each process appends its own
            # sink) — report --merge below must
            # fold both into one timeline
            aux = os.path.join(trace_dir, "aux.jsonl")
            with obs.MetricsLogger(aux) as lg:
                obs.log_counters(lg)
                obs.log_programs(lg)
        from dask_ml_tpu.observability.report import merge_records

        # `report --merge`: the span trace and the aux counters/programs
        # file fold into ONE timeline — the 8-device run renders as a
        # single report exactly like a multi-file multi-process round
        records = merge_records([load_records(trace), load_records(aux)])
        report = build_report(records, path=f"{trace} + {aux}")
        data = report_data(records)
        spans = [r["span"] for r in data["spans"]]
        programs = [p["program"] for p in data["programs"]]
        # the merged report must render the sharded fits' spans AND
        # their compiled programs — the assertion the dryrun exists for
        assert "LogisticRegression.fit" in spans, spans
        assert "spans (time by component)" in report
        assert "programs (XLA cost/memory per compiled entry point)" \
            in report
        assert any(p == "glm.lbfgs" for p in programs), programs
        assert any(p == "glm.admm" for p in programs), programs
        # the psum-bearing SHARDED superblock scan programs (ISSUE 9)
        # must rank in the same programs table — per-device attribution
        # of the streamed hot loop
        assert any(p == "superblock.sgd_scan.psum" for p in programs), \
            programs
        assert any(p == "superblock.glm.vg.psum" for p in programs), \
            programs
        # counters came from the aux file: the merge really folded both
        assert data["counters"].get("recompiles", 0) > 0, data["counters"]
        # the CLI flag itself renders the same merged timeline
        import contextlib
        import io

        from dask_ml_tpu.observability import report as report_cli

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = report_cli.main(["--merge", "--json", trace, aux])
        assert rc == 0, rc
        cli_data = json.loads(buf.getvalue())
        assert cli_data["merged_files"] == 2
        assert any(r["span"] == "LogisticRegression.fit"
                   for r in cli_data["spans"])
        out.update(
            ok=True,
            trace_records=len(records),
            merged_files=2,
            report_spans=spans,
            report_programs=programs,
        )
    except Exception:
        out["rc"] = 1
        out["tail"] = traceback.format_exc()[-2000:]
    print(json.dumps(out))
    return out["rc"]


if __name__ == "__main__":
    sys.exit(main())
