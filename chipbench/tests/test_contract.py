"""The benchmark's data hangs together, and a run without a TPU prints no
result."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_part_is_found_by_name():
    assert set(harness.load_benchmark()) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    bench = harness.load_benchmark(pending=True)
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = harness.Cell(bench, w["name"])
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        assert all(m["moves"] in reported for m in cell.per_layer)
        for fn in ("setup", "window", "finish"):
            assert callable(getattr(cell.driver, fn))
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and NAME.match(m["name"])
        reader = harness.load_module(
            harness.HERE / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "store-ycsb-c",
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
