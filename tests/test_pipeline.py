"""GPipe pipeline: forward equivalence + gradient match vs the plain stack
(subprocess with a 4-device "stage" mesh)."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def test_gpipe_matches_sequential():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import auto_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train.pipeline import make_pipelined_fn

        S, M, mb, d = 4, 8, 2, 16
        mesh = auto_mesh((S,), ("stage",))
        rng = np.random.default_rng(0)
        # each stage: one dense layer + tanh
        ws = jnp.asarray(rng.standard_normal((S, d, d)) * 0.3, jnp.float32)
        xs = jnp.asarray(rng.standard_normal((M, mb, d)), jnp.float32)

        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        def sequential(ws, xs):
            def per_mb(x):
                for i in range(S):
                    x = stage_fn(ws[i], x)
                return x
            return jax.vmap(per_mb)(xs)

        pipe = make_pipelined_fn(stage_fn, mesh, S)
        with jax.set_mesh(mesh):
            got = jax.jit(pipe)(ws, xs)
        want = sequential(ws, xs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

        # gradients flow through the schedule (ppermute is differentiable)
        def loss_pipe(ws):
            return jnp.sum(jax.jit(pipe)(ws, xs) ** 2)
        def loss_seq(ws):
            return jnp.sum(sequential(ws, xs) ** 2)
        with jax.set_mesh(mesh):
            g1 = jax.grad(loss_pipe)(ws)
        g2 = jax.grad(loss_seq)(ws)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=1e-4, rtol=1e-4)
        print("gpipe fwd+bwd equivalence ok")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=560, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "ok" in out.stdout
