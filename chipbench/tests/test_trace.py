"""Reduction of a profiler trace: busy/idle union, per-program time and
gap attribution, on a hand-made trace and on one recorded on a v5e."""
from pathlib import Path

import pytest

from chipbench import trace as T

DATA = Path(__file__).parent / "data"


def _hand_trace():
    E, H, D = T.Event, "/host:CPU", "/device:TPU:0"
    return [
        E(H, "python", "cb:window", 1000, 10000),
        E(H, "python", "cb:step", 1000, 5000),
        E(H, "python", "cb:forward", 1500, 1500),
        E(H, "python", "cb:wait_arrival", 6000, 5000),
        E(D, "XLA Modules", "jit_bloom_probe_pairs_ref(9)", 500, 700),
        E(D, "XLA Modules", "jit__layer_forward(3)", 2000, 500),
        E(D, "XLA Modules", "jit__layer_forward(3)", 2400, 400),
        E(D, "XLA Modules", "jit_x.2", 10500, 1500),
        E(D, "XLA Ops", "fusion.1", 2000, 500),
        E(D, "XLA Ops", "fusion.2", 2400, 400),
    ]


def test_busy_union_programs_and_gaps_by_hand():
    s = T.reduce_events(_hand_trace())
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(10000e-9)
    # [1000,1200] + [2000,2800] + [10500,11000]
    assert s["busy_s"] == pytest.approx(1500e-9)
    assert s["per_program"] == pytest.approx(
        {"_layer_forward": 900e-9, "bloom_probe_pairs_ref": 200e-9,
         "x": 500e-9})
    assert dict(s["device_ops"]) == pytest.approx(
        {"fusion.1": 500e-9, "fusion.2": 400e-9})
    # gap [1200,2000] lies in cb:forward (inside cb:step); gap
    # [2800,10500] has its middle in cb:wait_arrival
    assert dict(s["idle_gaps"]) == pytest.approx(
        {"forward": 800e-9, "wait_arrival": 7700e-9})


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        T.reduce_events([e for e in _hand_trace() if e.name != "cb:window"])


@pytest.mark.parametrize("name,want", [
    ("jit__layer_forward(123)", "_layer_forward"),
    ("jit_bloom_probe_pairs_ref", "bloom_probe_pairs_ref"),
    ("jit_convert_element_type.3", "convert_element_type"),
])
def test_program_names(name, want):
    assert T.program_name(name) == want


def _busy_by_bins(events, bin_ns=100.0):
    """Busy time by marking 100 ns bins: a second way to the union."""
    import numpy as np
    w = next(e for e in events if e.name == T.WINDOW_SPAN)
    bins = np.zeros(int(w.dur_ns / bin_ns) + 1, bool)
    for e in events:
        if e.plane.startswith("/device:") and e.line == T.MODULE_LINE:
            a = max(0, int((e.start_ns - w.start_ns) / bin_ns))
            b = min(len(bins), int((e.start_ns + e.dur_ns - w.start_ns)
                                   / bin_ns))
            bins[a:b] = True
    return bins.sum() * bin_ns * 1e-9


@pytest.mark.parametrize("name,program,gap", [
    ("store", "bloom_probe_pairs_ref", "probe_call"),
    ("serve", "_layer_forward", "forward"),
])
def test_recorded_v5e_trace(name, program, gap):
    """0.1 s cut from a traced window of each cell on one v5e (the cell's
    first chip call), with the window span narrowed to the cut."""
    events = T.read_saved(str(DATA / f"v5e_{name}_trace.json"))
    s = T.reduce_events(events)
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(_busy_by_bins(events), abs=2e-5)
    idle = sum(v for _, v in s["idle_gaps"])
    assert idle + s["busy_s"] == pytest.approx(s["window_s"])
    top = max(s["per_program"], key=s["per_program"].get)
    assert top == program
    assert sum(s["per_program"].values()) >= s["busy_s"] * 0.999
    assert max(s["idle_gaps"], key=lambda g: g[1])[0] == gap
    assert len(s["device_ops"]) <= T.TOP
