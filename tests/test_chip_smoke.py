"""``chip_smoke.py``: it refuses the CPU, fails without the repo, and its
phases run end to end at a tiny size on the CPU (kernels interpreted)."""

import os
import shutil
import subprocess
import sys
import textwrap
import time

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")

TINY = dict(num_layers=2, d_model=64, d_ff=128, vocab_size=256, head_dim=16,
            matmul_mode="bp_exact", matmul_backend="kernel_interpret")
TINY_RUN = dict(expect_backend="kernel_interpret", n_slots=4,
                prompt_lens=(5, 9, 16, 30, 31, 32), new_tokens=6)


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env.update(extra)
    return env


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_to_run_without_a_tpu(tmp_path, where):
    """Under JAX_PLATFORMS=cpu the script exits non-zero within seconds,
    builds no model and prints no result; copied into a directory that
    holds nothing else of the repo it fails the same way."""
    script = SCRIPT
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    t0 = time.perf_counter()
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, timeout=120, cwd=os.path.dirname(script),
                         env=_env(JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert time.perf_counter() - t0 < 60
    assert '"ok": true' not in out.stdout
    assert "model:" not in out.stdout
    if where == "checkout":
        assert out.returncode == 2
        assert "no TPU found" in out.stderr


def _tiny_cfg():
    from repro.configs.base import get_arch
    return get_arch("qwen2-1.5b").reduced().replace(**TINY)


def test_phases_run_at_tiny_size_on_cpu():
    """Kernel parity and the front-door main path, at a tiny size with the
    kernels interpreted: every check the chip run makes passes."""
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke as cs
    finally:
        sys.path.remove(ROOT)
    cs.matmul_parity(0, interpret=True, projections=((64, 128), (256, 64)),
                     ms=(8,))
    cs.attention_parity(0, interpret=True, B=3, pages_per_seq=4)
    cfg = _tiny_cfg()
    result = cs.main_path(cfg, cs.build_model(cfg, 0), 0, **TINY_RUN)
    assert result["tokens"] == 6 * len(TINY_RUN["prompt_lens"])
    assert result["logit_rel_err"] <= cs.LOGIT_REL_TOL


def test_mesh_phase_runs_on_four_virtual_devices():
    """The --chips 4 phase: mesh vs one device in one process, on four
    virtual CPU devices."""
    script = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as cs
        from repro.configs.base import get_arch
        cfg = get_arch("qwen2-1.5b").reduced().replace(**{TINY!r})
        r = cs.mesh_phase(cfg, cs.build_model(cfg, 0), 0, **{TINY_RUN!r})
        assert r["logit_rel_err"] <= cs.MESH_LOGIT_REL_TOL, r
        print("MESH OK", r)
    """)
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=600, cwd=ROOT,
        env=_env(PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "MESH OK" in out.stdout


@pytest.mark.parametrize("placed", ["env", "default"])
def test_compile_cache_placement(tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR, when set, is where compiles are cached and
    the helper sets no other directory; unset, the cache is the fixed
    git-ignored directory inside the checkout."""
    script = textwrap.dedent("""
        import os, jax, jax.numpy as jnp
        from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
        path = enable_compile_cache()
        print("PATH", path)
        print("CONFIG", jax.config.jax_compilation_cache_dir)
        print("DEFAULT", DEFAULT_DIR)
        if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.jit(lambda x: x * 2 + 1)(jnp.ones(4)).block_until_ready()
    """)
    env = _env(PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if placed == "env":
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=120, cwd=str(tmp_path), env=env)
    assert out.returncode == 0, out.stderr
    lines = dict(l.split(" ", 1) for l in out.stdout.splitlines())
    default = os.path.join(ROOT, ".jax_cache")
    assert lines["DEFAULT"] == default
    want = str(tmp_path / "cache") if placed == "env" else default
    assert lines["PATH"] == lines["CONFIG"] == want
    if placed == "env":
        assert os.listdir(want), "nothing was cached in JAX_COMPILATION_CACHE_DIR"
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split(), ".jax_cache is not ignored"
