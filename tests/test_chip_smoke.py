"""CPU rehearsal of ``chip_smoke.py`` (the script's ``__main__`` has no CPU
mode): its step functions at a tiny size on the 8-device virtual mesh, with
interpret-mode kernels and the choices the TPU auto-gates make requested
explicitly — so the control flow, the stats it reads and the reference it
checks against are exercised before any chip time is spent. Also the
start-up contracts the script leans on: the compile-cache placement and the
refusal to run off-chip."""

import os
import subprocess
import sys

import chip_smoke  # the repo root is on sys.path (tests/conftest.py)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 2048-row blocks on 8 devices: 256-row shard slabs — a 128-multiple, so
# the fused kernels' tile gate accepts them as it does on the chip
TINY = chip_smoke.Sizes(
    d=64, resident_rows=16_384, sample_rows=2_048, stream_rows=16_384,
    stream_block_rows=2_048, kernel_rows=1_024, n_classes=8, lloyd_k=8,
    lloyd_d=32, pca_rows=4_096, pca_d=96, pca_k=12,
)


def test_steps_rehearsal(tmp_path):
    """resident -> predict -> objective -> streamed, sharing state as
    ``main`` does."""
    state = {"memmap": chip_smoke.make_memmap(TINY, str(tmp_path))}
    facts = {}
    for name, step in chip_smoke.STEPS[:4]:
        facts[name] = step(TINY, interpret=True, state=state)
    assert facts["resident"]["fit_dtype"] == "bfloat16"
    assert facts["resident"]["fused"] is True
    assert facts["resident"]["loss_at_fit"] < facts["resident"]["loss_at_zero"]
    # the 8-shard vs 1-shard agreement, fused and XLA: the D-times check
    for flav in ("fused", "xla"):
        assert facts["objective"][f"{flav}/full-vs-one"]["grad"] <= 1e-5
    st = facts["streamed"]["stats"]
    assert st["sb_shards"] == 8
    assert st["native_reader"] is True and st["superblock_k"] > 1


def test_kernels_rehearsal():
    """Every kernel case in interpret mode against its XLA flavour."""
    facts = chip_smoke.step_kernels(TINY, interpret=True)
    assert len(facts) == len(chip_smoke.kernel_cases(TINY)) >= 20
    assert all(f["rel_err_vs_xla_highest"] <= chip_smoke.TOL_KERNEL
               for f in facts.values())


def test_pca_rehearsal():
    """The third family's step on the 8-device mesh: every reading inside
    the benchmark's band, X on every device."""
    facts = chip_smoke.step_pca(TINY, interpret=True)
    assert facts["shards"] == 8
    assert facts["solver_info"] == {"solver": "randomized", "size": 22,
                                    "n_iter": 2, "x_sweeps": 6,
                                    "qr_fallbacks": 0}
    assert facts["transform"] <= 1e-5 and facts["angle"] < facts["angle_band"]


def test_main_refuses_to_run_off_chip():
    """Off-chip the script exits non-zero naming the backend it found, and
    prints no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=_REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert "default backend here is 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_compile_cache_placed_from_outside(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the package sets NO directory in
    code (jax reads the variable itself); unset, the cache goes to the
    fixed ``<checkout>/.jax_cache``. The thresholds are zeroed either way."""
    import jax

    from dask_ml_tpu import config

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))

    monkeypatch.setenv(config.COMPILE_CACHE_ENV, "/some/dir")
    assert config.ensure_compile_cache() == "/some/dir"
    assert "jax_compilation_cache_dir" not in dict(calls)
    assert dict(calls) == {
        "jax_persistent_cache_min_compile_time_secs": 0.0,
        "jax_persistent_cache_min_entry_size_bytes": -1,
    }

    calls.clear()
    monkeypatch.delenv(config.COMPILE_CACHE_ENV)
    expected = os.path.join(_REPO, ".jax_cache")
    assert config.ensure_compile_cache() == expected
    assert dict(calls)["jax_compilation_cache_dir"] == expected
