"""Pipeline parallelism: GPipe over a 2-stage 'pod' axis must reproduce the
sequential layer stack exactly (subprocess: needs >1 device)."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_gpipe_matches_sequential():
    script = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel.pipeline import gpipe_forward, split_stages

        mesh = jax.make_mesh((2,), ("pod",))
        L, D, M, MB = 4, 16, 4, 2   # layers, width, microbatches, mb size
        ks = jax.random.split(jax.random.PRNGKey(0), L)
        params = {"w": jnp.stack([
            jax.random.normal(k, (D, D), jnp.float32) * 0.3 for k in ks])}

        def block_fn(lp, h):
            return jnp.tanh(h @ lp["w"])

        x = jax.random.normal(jax.random.PRNGKey(1), (M, MB, D), jnp.float32)

        # sequential reference
        ref = x
        for i in range(L):
            ref = jnp.tanh(ref @ params["w"][i])

        staged = split_stages(params, 2)
        fn = gpipe_forward(block_fn, mesh, n_microbatches=M)
        out = jax.jit(fn)(staged, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("GPIPE_OK")
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"    # virtual CPU devices, never the chip
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, timeout=560,
                         env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "GPIPE_OK" in out.stdout
