"""chip_smoke.py rehearsed on the CPU mesh: the three legs at tiny
sizes (Pallas kernels interpreted), the refusal to run without a chip,
and the compile-cache rule. The chip itself is exercised by running
``python chip_smoke.py`` where there is one."""

import os
import subprocess
import sys

import jax

import chip_smoke
from mpistragglers_jl_tpu.utils import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_leg_pool_tiny_four_devices():
    out = chip_smoke.leg_pool(
        jax.devices()[:4], m=48, kdim=32, ncols=16, epochs=2, delay_s=0.3
    )
    assert out["coded_gemm"]["worker_devices"] == 4
    assert all(f >= 6 for f in out["coded_gemm"]["fresh_at_return"])
    assert out["one_worker_per_chip"]["code"] == [4, 3]
    assert out["pool_mesh"]["decoded_shard_devices"] == 4


def test_leg_trainer_tiny_one_and_four_devices():
    out = chip_smoke.leg_trainer(
        jax.devices()[:4], vocab=128, d_model=64, n_heads=4, n_layers=2,
        d_ff=128, batch=2, seq=64,
    )
    assert out["four_chips"]["mesh"] == {"dp": 1, "sp": 2, "tp": 2}
    # interpreted here; on the chip the same count must be positive
    assert out["one_chip"]["mosaic_calls"] == 0


def test_leg_server_tiny():
    out = chip_smoke.leg_server(
        vocab=128, d_model=256, n_heads=2, n_kv_heads=1, n_layers=1,
        d_ff=256, window=64, slots=4, page_tokens=16, n_inner=2,
        prompt_chunk=16, max_prompt=64, prompt_lens=(5, 12, 20, 40),
        n_requests=6, max_new=6,
    )
    assert out["requests"] == 6 and out["mosaic_calls"] == 0


def test_entry_refuses_without_a_chip():
    res = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=_REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert res.returncode != 0
    assert "'cpu'" in res.stderr
    assert '"ok"' not in res.stdout


def test_compile_cache_follows_the_environment(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.wire_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(_REPO, ".jax_cache")
    assert compile_cache.wire_compile_cache() == fixed
    assert jax.config.jax_compilation_cache_dir == fixed
    jax.config.update("jax_compilation_cache_dir", before)
