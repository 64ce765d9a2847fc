"""chip_smoke.py off the chip: its phases at scaled_down size on the CPU.

``main()`` refuses any backend but a TPU; the phase functions it calls run
anywhere, so the CPU checks their control flow and their checks here.
"""

import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import REPO, run_py


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_a_cpu_backend():
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "not 'tpu'" in r.stderr


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_serve_phase_answers_every_request(smoke, fused):
    rec = smoke.serve_phase("chatglm3-6b", reduced=True, fused_decode=fused)
    assert rec["answered"] == rec["requests"] == 8
    assert rec["tokens"] == 8 * 32
    assert rec["dropped"] == 0
    assert rec["setup_s"] > 0 and rec["wall_s"] >= rec["setup_s"]


def test_fused_and_unfused_serve_agree_on_cpu(smoke):
    """Interpret mode keeps the engine-level token identity."""
    a = smoke.serve_phase("chatglm3-6b", reduced=True)["generated"]
    b = smoke.serve_phase("chatglm3-6b", reduced=True,
                          fused_decode=True)["generated"]
    assert smoke.token_agreement(a, b) == 1.0


def test_fused_kernel_phase_within_tolerance(smoke):
    rec = smoke.fused_kernel_phase("chatglm3-6b", reduced=True, max_len=160,
                                   require_kernel=False)
    assert not rec["kernel_in_step"]          # interpret mode: no Mosaic
    assert rec["max_abs_diff"]["out"] <= smoke.FUSED_ATOL
    assert rec["max_abs_diff"]["k_cache"] <= smoke.FUSED_ATOL


def test_fused_kernel_phase_requires_the_kernel(smoke):
    with pytest.raises(smoke.PhaseFailed, match="tpu_custom_call"):
        smoke.fused_kernel_phase("chatglm3-6b", reduced=True, max_len=32)


def test_token_agreement_counts_positions(smoke):
    a = {0: np.array([1, 2, 3, 4]), 1: np.array([5, 6])}
    b = {0: np.array([1, 2, 0, 4]), 1: np.array([5, 0])}
    assert smoke.token_agreement(a, b) == pytest.approx(4 / 6)


@pytest.mark.slow
def test_four_chip_phases_on_virtual_devices():
    """The --chips 4 path on four CPU devices: serve over a (1, 4) mesh,
    then one-device vs tensor-parallel prefill logits."""
    r = run_py(f"""
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke",
                                              {str(REPO / "chip_smoke.py")!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro.serve import WorkloadSpec
rec = smoke.serve_phase("granite-3-8b", reduced=True, mesh_shape=(1, 4),
                        spec=WorkloadSpec(**smoke.TP_SPEC))
assert rec["answered"] == rec["requests"] == 4, rec
assert len(rec["peak_bytes_in_use"]) == 4
tp = smoke.tp_logits_phase("granite-3-8b", reduced=True)
assert tp["max_abs_logit"] < 1e3, tp        # the -1e30 vocab padding is cut
assert tp["max_abs_diff"] <= smoke.TP_LOGIT_TOL * tp["max_abs_logit"], tp
print("OK", tp)
""", devices=4)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "OK" in r.stdout


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import jax
    from repro.runtime.flags import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_follows_the_environment(tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins, and compiled programs land there."""
    cache = tmp_path / "cache"
    r = run_py("""
import os
import jax, jax.numpy as jnp
from repro.runtime.flags import enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
path = enable_compile_cache()
assert path == os.environ["JAX_COMPILATION_CACHE_DIR"], path
assert jax.config.jax_compilation_cache_dir == path
jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
print("OK")
""", env_extra={"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert r.returncode == 0, r.stderr[-4000:]
    assert any(cache.iterdir())
