"""Both cells driven end to end on the CPU at tiny sizes, past the
harness's look for a chip: sound runs come out correct, and a run with the
timed path broken underneath comes out not correct."""
import json

import numpy as np
import pytest

from chipbench import run
from conftest import FAKE_PEAKS, smoke_serve_cell, tiny_store_cell


def _run(cell, capsys, seed=2 ** 31 + 11, seconds=2.0, trace=False):
    rc = run.run_cell(cell, seed, seconds, trace, require_chip=False,
                      peaks=FAKE_PEAKS)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_store_cell_is_correct(capsys):
    res = _run(tiny_store_cell(), capsys)
    assert res["correct"] is True, res["checks"]
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert set(res["metrics"]) == {"store_ops_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0


def test_store_traced_run_reads_per_layer_metrics(capsys):
    res = _run(tiny_store_cell(), capsys, trace=True)
    assert res["correct"] is True
    m = res["metrics"]
    assert m["store_probe_pairs_per_read"]["value"] > 0
    assert m["store_probe_call_ms"]["value"] > 0
    # the CPU has no device plane: no roofline is made up
    assert "probe_pairs_roofline" not in m
    assert "window_s" in res["device"]


def _flip_some_hits(monkeypatch):
    from repro.lsm import filters
    real = filters.probe_pairs_device

    def probe(*a):
        out = real(*a)
        return out.at[0].set(1 - out[0])

    monkeypatch.setattr(filters, "probe_pairs_device", probe)


def _alter_one_answer(monkeypatch):
    from repro.lsm.tree import LSMTree
    real = LSMTree.get_batch

    def get_batch(self, keys):
        res = yield from real(self, keys)
        res[0] = (not res[0][0], res[0][1])
        return res

    monkeypatch.setattr(LSMTree, "get_batch", get_batch)


@pytest.mark.parametrize("fault", [_flip_some_hits, _alter_one_answer])
def test_store_fault_is_not_correct(fault, monkeypatch, capsys):
    fault(monkeypatch)
    res = _run(tiny_store_cell(), capsys)
    assert res["correct"] is False


def test_serve_cell_is_correct(capsys):
    rc = run.run_cell(smoke_serve_cell(), 2 ** 31 + 11, 3.0, True,
                      require_chip=False, peaks=FAKE_PEAKS)
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    res, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert res["correct"] is True, res["checks"]
    # set-up warmed every program the window used
    assert info["programs_warmed"] > 0 and info["compiles_in_window"] == 0
    m = res["metrics"]
    assert m["serve_mfu"]["value"] > 0
    assert m["serve_itl_p95_ms"]["value"] > 0
    assert "serve_kv_migrated_bytes_per_token" in m
    assert "layer_forward_roofline" not in m


def _zero_host_page(monkeypatch):
    from repro.serving.paged_kv import PagedPool
    real = PagedPool.copy_zone_from

    def copy(self, other, src, dst):
        moved = real(self, other, src, dst)
        if isinstance(self.k, np.ndarray):
            self.k[:, dst.pages[0]] = 0.0
        return moved

    monkeypatch.setattr(PagedPool, "copy_zone_from", copy)


def _alter_tokens(monkeypatch):
    from repro.serving.engine import ServingEngine
    real = ServingEngine._forward_tokens

    def forward(self, req, tokens):
        return (real(self, req, tokens) + 1) % self.cfg.vocab_size

    monkeypatch.setattr(ServingEngine, "_forward_tokens", forward)


def _drop_kv_writes(monkeypatch):
    from repro.serving.paged_kv import PagedPool
    real = PagedPool.write_token

    def write(self, zone, k=None, v=None):
        return real(self, zone, None if k is None else k * 0,
                    None if v is None else v * 0)

    monkeypatch.setattr(PagedPool, "write_token", write)


def _bf16_kv_pool(monkeypatch):
    import jax.numpy as jnp
    from repro.serving.paged_kv import PagedPool
    real = PagedPool.__init__

    def init(self, *a, **kw):
        real(self, *a, **kw)
        if self.materialize:
            self.k, self.v = (t.astype(jnp.bfloat16) for t in (self.k, self.v))

    monkeypatch.setattr(PagedPool, "__init__", init)


@pytest.mark.parametrize("fault", [_zero_host_page, _alter_tokens,
                                   _drop_kv_writes, _bf16_kv_pool])
def test_serve_fault_is_not_correct(fault, monkeypatch, capsys):
    fault(monkeypatch)
    cell = smoke_serve_cell(rate=8.0)
    # two HBM zones besides the prefix cache's, so that the window demotes
    # a sequence to the host under any request order
    cell.config["engine"]["hbm_zones"] = 3
    res = _run(cell, capsys, seconds=3.0)
    assert res["correct"] is False, res["checks"]
