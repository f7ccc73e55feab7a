"""Compile the device paths at real widths for one described TPU v5e chip.

Nothing runs: each test lowers and compiles for a chip of the ``v5e:2x2``
topology, which the installed TPU compiler can describe without one
attached, so what that compiler refuses fails here first.  The topology
is described inside a fixture (never at import), and these compiles stay
in this one file: only one process at a time may load the TPU library.
"""
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.lsm import filters  # noqa: E402

QWEN3 = get_config("qwen3-1.7b")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to compile
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_store_probe_compiles_at_full_load_bucket(one_chip):
    """The store's jitted pairs probe at the bucket a full paper-scale
    load reaches (~655k filter words -> 2**20) with 4,096 pairs."""
    from repro.kernels.bloom_probe.ops import probe_pairs
    from repro.lsm import ScenarioConfig
    sc = ScenarioConfig()
    words = sc.paper_keys * sc.lsm.filter_bits_per_key // 32
    pw = filters.bucket(words, filters.MIN_WORDS_BUCKET)
    pp = filters.bucket(4096, filters.MIN_PAIRS_BUCKET)
    assert pw == 2 ** 20
    args = [_spec((pp,), np.uint32, one_chip), _spec((pp,), np.uint32, one_chip),
            _spec((pp,), np.int32, one_chip), _spec((pp,), np.uint32, one_chip),
            _spec((pw,), np.uint32, one_chip)]
    compiled = probe_pairs.lower(*args, k_hashes=7).compile()
    assert compiled.as_text()


def test_paged_attention_compiles_at_qwen3_decode_widths(one_chip):
    from repro.kernels.paged_attention.ops import paged_attention
    b, pages, ps, max_pages = 4, 256, 16, 64
    h, kv, d = QWEN3.num_heads, QWEN3.num_kv_heads, QWEN3.head_dim_
    compiled = paged_attention.lower(
        _spec((b, h, d), jnp.bfloat16, one_chip),
        _spec((pages, ps, kv, d), jnp.bfloat16, one_chip),
        _spec((pages, ps, kv, d), jnp.bfloat16, one_chip),
        _spec((b, max_pages), jnp.int32, one_chip),
        _spec((b,), jnp.int32, one_chip), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_qwen3_widths(one_chip):
    from repro.kernels.flash_attention.flash_attention import \
        flash_attention_fwd
    s = 2048
    h, kv, d = QWEN3.num_heads, QWEN3.num_kv_heads, QWEN3.head_dim_
    fwd = jax.jit(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True,
                                                      interpret=False))
    compiled = fwd.lower(_spec((1, h, s, d), jnp.bfloat16, one_chip),
                         _spec((1, kv, s, d), jnp.bfloat16, one_chip),
                         _spec((1, kv, s, d), jnp.bfloat16, one_chip)
                         ).compile()
    assert "tpu_custom_call" in compiled.as_text()
