"""repro.launch.train on the devices present, and the TPU interpret guard.

The launcher runs in subprocesses (it sets the compile cache and, with
--devices, the platform and device count, which are process-wide)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
SMOKE = ["--arch", "whisper-tiny", "--smoke", "--per-worker-batch", "2",
         "--seq", "16", "--log-every", "2"]


def _python(code: str, env_extra=None, timeout: int = 300) -> str:
    env = dict(os.environ, PYTHONPATH=SRC, REPRO_CHECK="1")
    env.pop("XLA_FLAGS", None)
    env.update(env_extra or {})
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


def test_train_colocated_workers_on_one_device(tmp_path):
    """One device, W=2: the single-device step with all workers on it,
    invariants checked, compile cache in $JAX_COMPILATION_CACHE_DIR, keyed
    by the program's metadata (the layer scopes) too."""
    out = _python(f"""
        import jax
        from repro.launch import train
        train.main({SMOKE + ["--workers", "2", "--steps", "4"]!r})
        print("cache_dir", jax.config.jax_compilation_cache_dir)
        print("keyed by metadata",
              jax.config.jax_compilation_cache_include_metadata_in_key)
        """, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert "mesh: {'worker': 1, 'fsdp': 1, 'model': 1}" in out, out
    assert "REPRO_CHECK: wire accounting + edge mirrors OK" in out, out
    assert f"cache_dir {tmp_path}" in out, out
    assert "keyed by metadata True" in out, out
    assert any(tmp_path.iterdir()), "no compiled program was cached"


def test_train_emulated_mesh_one_worker_per_device():
    """--devices 4, W=4 at 4 bits: the sharded step over a (4, 1, 1) mesh
    with the nibble-packed wire; the cache falls back to <repo>/.jax_cache."""
    out = _python(f"""
        import jax
        from repro.launch import train
        train.main({SMOKE + ["--devices", "4", "--workers", "4", "--bits",
                             "4", "--steps", "2"]!r})
        print("cache_dir", jax.config.jax_compilation_cache_dir)
        print("devices", len(jax.devices()), jax.default_backend())
        """, {"JAX_COMPILATION_CACHE_DIR": ""})
    assert "mesh: {'worker': 4, 'fsdp': 1, 'model': 1}" in out, out
    assert "REPRO_CHECK: wire accounting + edge mirrors OK" in out, out
    assert "devices 4 cpu" in out, out
    repo = os.path.abspath(os.path.join(SRC, ".."))
    assert f"cache_dir {os.path.join(repo, '.jax_cache')}" in out, out


def test_worker_mesh_from_present_devices():
    out = _python("""
        import jax
        from repro.launch.train import worker_mesh
        d = jax.devices()
        assert len(d) == 8
        shape = lambda m: tuple(m.shape.values())
        assert shape(worker_mesh(d, 2)) == (2, 1, 4)
        assert shape(worker_mesh(d, 8)) == (8, 1, 1)
        assert shape(worker_mesh(d, 3)) == (3, 1, 2)     # 2 devices idle
        assert shape(worker_mesh(d[:1], 4)) == (1, 1, 1)  # co-located
        m = worker_mesh(d[:4], 4)
        assert list(m.devices.reshape(-1)) == d[:4]
        try:
            worker_mesh(d[:3], 4)
        except ValueError as e:
            print("refused:", e)
        print("OK")
        """, {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert "refused: 4 workers on 3 devices" in out, out
    assert "OK" in out


def test_interpret_mode_refused_on_tpu_backend(monkeypatch):
    """On a TPU backend nothing may fall back to the Pallas interpreter:
    the trainer config and every kernel entry point refuse it."""
    from repro.core.gadmm import GADMMConfig
    from repro.core.quantizer import QuantizerConfig
    from repro.dist.qgadmm import DistConfig
    from repro.kernels.pack import ops as pack_ops
    from repro.kernels.quantize import ops as q_ops

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    gcfg = GADMMConfig(rho=1.0, quantize=True, qcfg=QuantizerConfig(bits=4))
    with pytest.raises(ValueError, match="interpret"):
        DistConfig(num_workers=2, gadmm=gcfg, wire_impl="pallas")
    DistConfig(num_workers=2, gadmm=gcfg, wire_impl="pallas_compiled")
    x = jnp.ones((5, 3))          # shapes no other test traces
    with pytest.raises(ValueError, match="interpret"):
        q_ops.quantize_dequantize(x, x, jax.random.PRNGKey(0), 1.0, 4,
                                  impl="pallas")
    with pytest.raises(ValueError, match="interpret"):
        pack_ops.pack4(jnp.zeros((77,), jnp.uint8), impl="pallas")
    with pytest.raises(ValueError, match="interpret"):
        pack_ops.unpack4(jnp.zeros((128,), jnp.uint8), 77, impl="pallas")
