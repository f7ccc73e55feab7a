"""The program's spans in a trace (``chipbench/program_spans.py``), the
readers of the metrics made from them, and a traced store run with them
(``chipbench/trace_program.py``), on the CPU."""
import json
from pathlib import Path

import pytest

from chipbench import harness, program_spans as P, trace as T
from conftest import FAKE_PEAKS, tiny_store_cell

DATA = Path(__file__).parent / "data"


def _hand_trace():
    """A window of 10,000 ns on one host thread: a probe call whose pad
    and device call lie inside it, and a compaction merge with an SST
    build and its filter build; the device runs twice."""
    E, H, D = T.Event, "/host:CPU", "/device:TPU:0"
    return [
        E(H, "python", "cb:window", 1000, 10000),
        E(H, "python", "hhzs:get_batch.level", 1000, 4000),
        E(H, "python", "hhzs:probe", 1500, 3000),
        E(H, "python", "cb:probe_call", 1600, 2600),
        E(H, "python", "hhzs:probe.pad", 1600, 400),
        E(H, "python", "hhzs:probe.call", 2000, 200),
        E(H, "python", "hhzs:compaction.merge", 6000, 1000),
        E(H, "python", "hhzs:sst.build", 7000, 2000),
        E(H, "python", "hhzs:filter.build", 7500, 1000),
        E(H, "other", "hhzs:hint", 6000, 500),        # another thread
        E(D, "XLA Modules", "jit_bloom_probe_pairs_ref(9)", 2200, 1000),
        E(D, "XLA Modules", "jit_bloom_probe_pairs_ref(9)", 8000, 200),
    ]


def test_span_self_times_by_hand():
    s = P.span_times(_hand_trace())
    ns = 1e-9
    want = {"cb:window": (10000, 10000 - 4000 - 1000 - 2000),
            "hhzs:get_batch.level": (4000, 1000),
            "hhzs:probe": (3000, 400),
            "cb:probe_call": (2600, 2000),
            "hhzs:probe.pad": (400, 400), "hhzs:probe.call": (200, 200),
            "hhzs:compaction.merge": (1000, 1000),
            "hhzs:sst.build": (2000, 1000),
            "hhzs:filter.build": (1000, 1000)}
    assert set(s) == set(want)          # the other thread's span is left out
    for name, (total, self_) in want.items():
        assert s[name]["count"] == 1
        assert s[name]["total_s"] == pytest.approx(total * ns)
        assert s[name]["self_s"] == pytest.approx(self_ * ns)
    # every host instant of the window is the self time of one span
    assert sum(v["self_s"] for v in s.values()) == pytest.approx(10000 * ns)


def test_gaps_split_over_the_innermost_spans_of_either_kind():
    s = P.summarize(_hand_trace())
    ns = 1e-9
    # device idle in [1000,2200], [3200,8000] and [8200,11000]
    assert dict(s["idle_gaps"]) == pytest.approx(
        {"hhzs:get_batch.level": 1000 * ns, "hhzs:probe": 400 * ns,
         "hhzs:probe.pad": 400 * ns, "hhzs:probe.call": 200 * ns,
         "probe_call": 1000 * ns, "hhzs:compaction.merge": 1000 * ns,
         "hhzs:sst.build": 1000 * ns, "hhzs:filter.build": 800 * ns,
         P.OUTSIDE: 3000 * ns})
    idle = sum(v for _, v in s["idle_gaps"])
    assert idle + s["busy_s"] == pytest.approx(s["window_s"])
    outside = dict(s["idle_gaps"])[P.OUTSIDE]
    assert outside <= s["spans"]["cb:window"]["self_s"] + 1e-15


@pytest.mark.parametrize("name", ["store", "serve"])
def test_recorded_v5e_traces_keep_every_summary_key(name):
    events = T.read_saved(str(DATA / f"v5e_{name}_trace.json"))
    old, new = T.reduce_events(events), P.summarize(events)
    for key in ("devices", "window_s", "busy_s", "per_program",
                "device_ops"):
        assert new[key] == old[key]
    # the same idle time, split over the harness's spans where the old
    # reduction gives each gap to the span around its middle
    idle = sum(v for _, v in new["idle_gaps"])
    assert idle == pytest.approx(sum(v for _, v in old["idle_gaps"]))
    assert new["idle_gaps"][0][0] == old["idle_gaps"][0][0]
    outside = dict(new["idle_gaps"]).get(P.OUTSIDE, 0.0)
    assert outside <= new["spans"]["cb:window"]["self_s"] + 1e-12
    assert new["spans"]["cb:window"]["total_s"] == pytest.approx(
        old["window_s"])
    assert sum(v["self_s"] for v in new["spans"].values()) == \
        pytest.approx(old["window_s"])


def _reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py")


_SPANS = {"cb:window": {"count": 1, "total_s": 10.0, "self_s": 6.0},
          "hhzs:probe.pad": {"count": 4, "total_s": 0.002, "self_s": 0.002},
          "hhzs:hint": {"count": 3, "total_s": 0.1, "self_s": 0.1},
          "hhzs:sst.build": {"count": 2, "total_s": 0.4, "self_s": 0.1},
          "hhzs:filter.build": {"count": 2, "total_s": 0.3, "self_s": 0.3},
          "hhzs:compaction.merge": {"count": 1, "total_s": 0.5,
                                    "self_s": 0.5}}
_CTX = {"counters": {"filter_probes": 30, "gets": 10, "probe_calls": 4,
                     "probe_h2d_bytes": 4 * 2 ** 20, "scheduled": 900,
                     "ops_completed": 10},
        "trace": {"window_s": 10.0, "busy_s": 0.1, "spans": _SPANS}}


@pytest.mark.parametrize("name,want,missing", [
    ("store_probe_h2d_bytes_per_call", 2 ** 20,
     {"counters": {"gets": 10}}),
    ("store_probe_pad_ms", 0.5, {"counters": {}, "trace": None}),
    ("store_background_share", 10.0, {"counters": {},
                                      "trace": {"window_s": 10.0}}),
    ("store_des_self_share", 60.0, {"counters": {}, "trace": None}),
    ("store_des_events_per_op", 90.0, {"counters": {"ops_completed": 0,
                                                    "scheduled": 5}}),
])
def test_new_readers(name, want, missing):
    read = _reader(name).read
    assert read(_CTX) == pytest.approx(want)
    assert read(missing) is None


def test_traced_store_run_reads_program_spans(capsys):
    from chipbench import run, trace_program
    cell = tiny_store_cell()
    trace_program.instrument(cell)
    assert run.run_cell(cell, 2 ** 31 + 5, 2.0, True, require_chip=False,
                        peaks=FAKE_PEAKS) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    res, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert res["correct"] is True
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # each call hands over its pairs, padded to at least the smallest
    # bucket (four 256-entry arrays of 4 B); a level image moves only when
    # it is uploaded to stay on the device, less than 4 KiB a call on
    # average here
    assert 16 * 256 <= m["store_probe_h2d_bytes_per_call"] \
        <= 16 * 256 + 4 * 1024
    assert m["store_probe_pad_ms"] > 0
    assert 0 < m["store_des_self_share"] < 100
    assert 0 <= m["store_background_share"] < 100
    assert m["store_des_events_per_op"] > 1
    assert info["traced_reads_per_s"] > 0 and info["untraced_reads_per_s"] > 0
