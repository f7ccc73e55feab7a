"""``chip_smoke.py``'s phases at tiny sizes on the CPU backend.

The script is the proof that the store and the serving engine run on a
TPU; here the same phase functions run on whatever backend the tests have,
so a wrong path, argument or check fails before any chip time is spent.
"""
import importlib.util
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from conftest import tiny_scenario  # noqa: E402


@pytest.fixture(scope="module")
def chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_check_raises_off_tpu(chip_smoke):
    platform = jax.devices()[0].platform
    if platform == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(RuntimeError, match=platform):
        chip_smoke.device_check("tpu")
    assert chip_smoke.device_check(platform)["count"] == 1


def test_store_phase_checks_every_probe(chip_smoke):
    out = chip_smoke.store_phase(tiny_scenario(), 3000, n_reads=1024,
                                 n_writes=128, open_loop_rate=2000.0,
                                 open_loop_s=1.0,
                                 platform=jax.devices()[0].platform)
    assert out["keys_loaded"] == 3000
    assert 0 < out["read_hits"] < out["reads"]      # absent keys miss
    assert out["device_probe_calls"] > 0
    assert out["probe_shapes_compiled"] <= chip_smoke.MAX_PROBE_SHAPES
    assert out["open_loop_ops"] > 0


def test_store_phase_catches_a_wrong_hit_mask(chip_smoke, monkeypatch):
    """The per-probe check is live: a device mask that differs from the
    numpy probe in one pair fails the phase."""
    from repro.lsm import filters
    real = filters.probe_pairs_device

    def flipped(*args):
        out = real(*args)
        return out.at[0].set(1 - out[0])
    monkeypatch.setattr(filters, "probe_pairs_device", flipped)
    with pytest.raises(AssertionError, match="differs from numpy"):
        chip_smoke.store_phase(tiny_scenario(), 1000, n_reads=256,
                               n_writes=16, platform=jax.devices()[0].platform)


def test_serving_phase_matches_dense_reference(chip_smoke):
    from repro.configs import get_config
    out = chip_smoke.serving_phase(get_config("qwen3-1.7b").smoke(),
                                   n_requests=2, prompt_len=16, new_tokens=4,
                                   hbm_zones=3, host_zones=16,
                                   pages_per_zone=2, page_size=8)
    assert out["tokens_out"] == 2 * 4
    assert out["demotions"] >= 1
    assert out["worst_gap"] <= chip_smoke.LOGIT_TOL
    assert 0 <= out["max_rivals_in_tol"] < out["tokens_out"]


def _stale_first_page(real):
    """A paging fault: the sequence's first page reads its second's KV."""
    def gather(self, req):
        k, v = real(self, req)
        ps = self.page_size
        if k.shape[1] >= 2 * ps:
            k = k.at[:, :ps].set(k[:, ps:2 * ps])
            v = v.at[:, :ps].set(v[:, ps:2 * ps])
        return k, v
    return gather


def _zeroed_host_tier(real):
    """A tiering fault: a demoted sequence's KV reads as zeros."""
    def gather(self, req):
        k, v = real(self, req)
        if self.mgr.seqs[req.rid].tier == "host":
            return jnp.zeros_like(k), jnp.zeros_like(v)
        return k, v
    return gather


@pytest.mark.parametrize("fault", [_stale_first_page, _zeroed_host_tier])
def test_serving_phase_catches_corrupt_kv(chip_smoke, monkeypatch, fault):
    """The token check is live: KV gathered from a wrong page, or a host
    tier that lost a demoted sequence, fails the phase."""
    from repro.configs import get_config
    from repro.serving import ServingEngine
    monkeypatch.setattr(ServingEngine, "_gather_kv",
                        fault(ServingEngine._gather_kv))
    with pytest.raises(AssertionError, match="below the reference"):
        chip_smoke.serving_phase(get_config("qwen3-1.7b").smoke(),
                                 n_requests=2, prompt_len=16, new_tokens=4,
                                 hbm_zones=3, host_zones=16,
                                 pages_per_zone=2, page_size=8)


def test_compile_cache_follows_env_else_fixed_checkout_dir(monkeypatch,
                                                          tmp_path):
    from repro import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # left to JAX
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(Path(__file__).resolve().parents[1]
                           / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_importing_repro_and_the_cache_helper_leaves_jax_unloaded():
    import subprocess
    import sys
    src = Path(__file__).resolve().parents[1] / "src"
    code = ("import sys, repro, repro.compile_cache, repro.lsm; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   env={"PYTHONPATH": str(src)})
