"""CPU rehearsal of the chip benchmark at tiny sizes.

    python -m pytest chipbench/tests

The cells' own drivers run here on a tiny store scenario and on the
``qwen3-1.7b`` smoke widths; no test reaches a chip."""
import copy
import os
import sys
import tempfile
from dataclasses import asdict
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness  # noqa: E402

# the rehearsal's compiled programs stay out of the checkout's cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="chipbench-test-cache-"))

FAKE_PEAKS = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 394e12,
              "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2 ** 30}


def tiny_scenario():
    """The repo's tiny test scenario (64-object SSTs), as a config group."""
    from repro.lsm import ScenarioConfig
    from repro.lsm.tree import LSMConfig
    from repro.zoned.device import MiB
    lsm = LSMConfig(
        obj_size=1024, block_size=4096, sst_size=int(0.0632 * MiB),
        memtable_size=int(0.032 * MiB),
        level_targets=(int(0.0632 * MiB),) * 2
        + (int(0.632 * MiB), int(6.32 * MiB), int(63.2 * MiB)),
        block_cache_blocks=8, filter_impl="jax")
    sc = ScenarioConfig(ssd_zones=20, ssd_zone_cap=int(0.0673 * MiB),
                        hdd_zones=4000, hdd_zone_cap=int(0.016 * MiB),
                        lsm=lsm)
    d = asdict(sc)
    d["lsm"]["level_targets"] = list(d["lsm"]["level_targets"])
    return d


def tiny_store_cell(objects=2000):
    cell = harness.Cell(harness.load_benchmark(), "store-ycsb-c")
    cell.config = dict(cell.config, objects=objects, scenario=tiny_scenario())
    cell.traffic = dict(cell.traffic, rate_ops_per_virtual_s=200.0,
                        virtual_s_per_wall_s=2.0, warmup_wall_s=0.5,
                        readback_keys=128, trace_seconds=1.0)
    return cell


def smoke_serve_cell(rate=4.0):
    cell = harness.Cell(harness.load_benchmark(pending=True),
                        "serve-chat-tiered")
    conf = copy.deepcopy(cell.config)
    conf["registry_name"] = "qwen3-1.7b-smoke"
    conf["hf_config"].update(num_hidden_layers=2, hidden_size=64,
                             num_attention_heads=4, num_key_value_heads=2,
                             head_dim=16, intermediate_size=128,
                             vocab_size=256)
    conf["engine"].update(hbm_zones=4, host_zones=32, pages_per_zone=2,
                          page_size=8)
    cell.config = conf
    cell.traffic = dict(cell.traffic, rate_per_s=rate, check_requests=64,
                        trace_seconds=0.5, warmup_new_tokens=4,
                        prompt=dict(median=12, sigma=0.6, min=8, max=32,
                                    multiple=8),
                        output=dict(median=6, sigma=0.5, min=2, max=10))
    return cell


@pytest.fixture
def store_cell():
    return tiny_store_cell()


@pytest.fixture
def serve_cell():
    return smoke_serve_cell()
