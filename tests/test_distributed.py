"""Distribution-layer tests.  Sharded execution needs >1 device, and jax
locks the device count at first init — so these run in subprocesses with
XLA_FLAGS set (the same mechanism as launch/dryrun.py, which must never leak
into the main test process)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, n_devices: int = 8, timeout: int = 560):
    env = dict(os.environ)
    # the virtual devices are CPU devices: never let a child reach for an
    # accelerator the parent process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env, cwd=REPO)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """A reduced arch train step on a 2×4 mesh must produce the same loss
    as unsharded execution (SPMD correctness of the sharding rules)."""
    stdout = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.models import build_model
        from repro.parallel.sharding import axis_rules, param_sharding, resolve
        from repro.train.optimizer import make_optimizer

        cfg = get_config("qwen3-8b").reduced().replace(
            dtype="float32", remat="none", d_model=64, n_heads=4,
            n_kv_heads=4, head_dim=16, d_ff=128)
        model = build_model(cfg)
        params, specs = model.init(jax.random.PRNGKey(0))
        opt = make_optimizer("adamw")
        opt_state = opt.init(params)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 200)

        def step(p, o, t):
            loss, grads = jax.value_and_grad(model.loss_fn)(p, {"tokens": t})
            p2, o2 = opt.update(grads, o, p)
            return loss, p2

        # single-device reference
        loss_ref, params_ref = jax.jit(step)(params, opt_state, tokens)

        mesh = make_mesh((2, 4), ("data", "model"))
        with axis_rules(mesh):
            _, sp = model.abstract_params()
            p_sh = param_sharding(sp, mesh,
                shapes=jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params))
            params_s = jax.device_put(params, p_sh)
            opt_s = jax.device_put(opt_state, jax.tree_util.tree_map(
                lambda _: None, opt_state)) if False else opt_state
            loss_sh, params_sh = jax.jit(step)(params_s, opt_s, tokens)
        np.testing.assert_allclose(float(loss_ref), float(loss_sh),
                                   rtol=2e-5)
        for a, b in zip(jax.tree_util.tree_leaves(params_ref),
                        jax.tree_util.tree_leaves(params_sh)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)
        print("SHARDED_OK", float(loss_ref))
    """)
    assert "SHARDED_OK" in stdout


def test_dryrun_cell_small_mesh():
    """dryrun_cell end-to-end on a small mesh (reduced device count): lower,
    compile, cost/memory analysis, collective parse."""
    stdout = _run("""
        import repro.launch.dryrun as dr
        import jax
        # monkeypatch the production mesh to the available 8 devices
        import repro.launch.mesh as mesh_mod
        mesh_mod.make_production_mesh = \
            lambda multi_pod=False: mesh_mod.make_mesh(
                (2, 2, 2) if multi_pod else (2, 4),
                ("pod", "data", "model") if multi_pod else ("data", "model"))
        dr.make_production_mesh = mesh_mod.make_production_mesh
        from repro.configs import get_config
        import repro.configs.base as base
        # shrink the shape grid for the test
        base.SHAPES["train_4k"] = base.ShapeSpec("train_4k", 64, 8, "train")
        rec = dr.dryrun_cell("tinyllama-1.1b", "train_4k",
                             overrides={"n_layers": 2, "d_model": 64,
                                        "n_heads": 4, "n_kv_heads": 4,
                                        "head_dim": 16, "d_ff": 128,
                                        "vocab_size": 256},
                             verbose=False)
        assert rec["flops_per_device"] > 0
        assert rec["bytes_accessed_per_device"] > 0
        assert rec["n_chips"] == 8
        import json
        print("DRYRUN_OK", json.dumps(
            {k: rec[k] for k in ("flops_per_device", "n_chips")}))
        # multi-pod variant
        rec2 = dr.dryrun_cell("tinyllama-1.1b", "train_4k", multi_pod=True,
                              overrides={"n_layers": 2, "d_model": 64,
                                         "n_heads": 4, "n_kv_heads": 4,
                                         "head_dim": 16, "d_ff": 128,
                                         "vocab_size": 256},
                              verbose=False)
        assert rec2["n_chips"] == 8 and rec2["mesh"]["pod"] == 2
        print("MULTIPOD_OK")
    """)
    assert "DRYRUN_OK" in stdout and "MULTIPOD_OK" in stdout


def test_collective_parser():
    from repro.launch.dryrun import collective_bytes_from_hlo
    hlo = """
      %all-reduce.1 = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %x)
      %ag = bf16[64]{0} all-gather(bf16[32]{0} %y), dimensions={0}
      %cp.2 = f32[16,16]{1,0} collective-permute(f32[16,16]{1,0} %z)
      %add.5 = f32[2]{0} add(f32[2]{0} %a, f32[2]{0} %b)
      %ars = f32[8]{0} all-reduce-start(f32[8]{0} %w)
    """
    out = collective_bytes_from_hlo(hlo)
    assert out["all-reduce"]["bytes"] == 128 * 256 * 4 + 8 * 4
    assert out["all-reduce"]["count"] == 2
    assert out["all-gather"]["bytes"] == 64 * 2
    assert out["collective-permute"]["bytes"] == 16 * 16 * 4
    assert out["all-to-all"]["count"] == 0


def test_roofline_math():
    from repro.launch.roofline import analyze_record, PEAK_FLOPS, HBM_BW
    from repro.configs.base import SHAPES
    rec = {
        "arch": "tinyllama-1.1b", "shape": "train_4k", "kind": "train",
        "multi_pod": False, "n_chips": 256,
        "flops_per_device": PEAK_FLOPS,            # exactly 1 second
        "bytes_accessed_per_device": HBM_BW / 2,   # 0.5 s
        "collective_bytes_per_device": 0,
        "collectives": {},
    }
    a = analyze_record(rec, SHAPES)
    assert abs(a["t_compute_s"] - 1.0) < 1e-9
    assert abs(a["t_memory_s"] - 0.5) < 1e-9
    assert a["dominant"] == "compute"
    assert 0 < a["model_over_hlo"] < 1.0
