"""GPipe pipeline primitive: exact equivalence with the sequential stack,
on 4 virtual devices (subprocess, per the XLA_FLAGS rule)."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"


@pytest.mark.slow
def test_pipeline_matches_sequential_and_is_differentiable():
    _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_apply

        S, B, D = 4, 8, 16
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (S, D, D)) / jnp.sqrt(D)
        x = jax.random.normal(jax.random.fold_in(key, 1), (B, D))

        def stage(params, h):
            return jnp.tanh(h @ params)

        mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("model",))

        def pipe(w, x):
            return pipeline_apply(stage, w, x, mesh=mesh,
                                  axis_name="model", n_microbatches=4)

        got = jax.jit(pipe)(w, x)
        want = x
        for s in range(S):
            want = stage(w[s], want)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

        # differentiable end to end (ppermute transposes correctly)
        g = jax.grad(lambda w: jnp.sum(pipe(w, x) ** 2))(w)
        g_ref = jax.grad(lambda w: jnp.sum(
            jnp.tanh(jnp.tanh(jnp.tanh(jnp.tanh(x @ w[0]) @ w[1]) @ w[2])
                     @ w[3]) ** 2))(w)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   atol=1e-5, rtol=1e-4)
        print("OK pipeline")
    """)
