"""Process entry points: the compile-cache placement and the serving
launcher (``python -m repro.launch.serve``) at its CPU size."""

import jax
import pytest

from repro.launch import compile_cache, serve


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    assert (compile_cache.DEFAULT_DIR.parent / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == path
    # the same path on every call: nothing of the process goes into it
    assert compile_cache.enable_compile_cache() == path


def test_compile_cache_leaves_the_environment_setting_to_jax(
        monkeypatch, restore_cache_dir, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX read the variable itself at start-up; the code set no path
    assert jax.config.jax_compilation_cache_dir is None


def test_random_prompts_are_seeded_and_in_range():
    a = serve.random_prompts(3, 5, (4, 9), 50)
    assert a == serve.random_prompts(3, 5, (4, 9), 50)
    assert a != serve.random_prompts(4, 5, (4, 9), 50)
    assert all(4 <= len(p) <= 9 and 0 < min(p) and max(p) < 50 for p in a)


def test_chip_smoke_counts_kernel_error_in_bf16_ulps():
    """A one-ulp rounding difference reads 1 at any magnitude >= 1 and
    less below it, where the ulp of 1 is the unit."""
    import importlib.util
    from pathlib import Path

    import jax.numpy as jnp

    path = Path(compile_cache.__file__).resolve().parents[3] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)

    want = jnp.asarray([3.0, 0.25, 1.0, -2.0], jnp.bfloat16)
    got = jnp.asarray([3.0 + 2 ** -6, 0.25 + 2 ** -9, 1.0 + 2 ** -7, -2.0],
                      jnp.bfloat16)
    assert chip_smoke.errors(jnp, got, want) == (2 ** -6, 1.0)
    assert chip_smoke.errors(jnp, got.at[3].add(-2 ** -5), want) == (
        2 ** -5, 2.0)


def test_warm_compiles_the_decode_step():
    """session.warm() compiles the decode step, so serving compiles it no
    more; decode_hlo() hands back that program's optimized HLO."""
    from repro.serving import ServingConfig
    from repro.serving import serve as open_session

    model, params = serve.build("tinyllama-1.1b", 0, reduced=True)
    config = ServingConfig(num_pages=32, page_size=8, max_batch=2,
                           max_seq_len=32, prefill_chunk_tokens=16,
                           scheduler="packed")
    with open_session(model, params, config) as session:
        shard = session.engine.shards[0]
        session.warm()
        assert shard._decode._cache_size() == 1
        out = session.submit([3, 1, 4, 1, 5], max_new_tokens=4).result(
            timeout=120)
        assert len(out) == 4
        assert shard._decode._cache_size() == 1
        assert "HloModule" in shard.decode_hlo()


def test_serve_launcher_reduced(capsys, monkeypatch, restore_cache_dir,
                                tmp_path):
    """The launcher's whole path at the reduced size: build from a seed,
    warm, serve every request, print the session totals."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    serve.main(["--reduced", "--requests", "3"])
    out = capsys.readouterr().out
    assert "tinyllama-1.1b-reduced" in out and "backend=xla" in out
    assert "'completed': 3" in out and "'failed': 0" in out
