"""The controls at a size a test run holds: the serving reference with
its weights rounded below the configuration's bf16 (int8, float8) or its
K/V held in bfloat16 below the stated float32, and a store whose device
probe rejects keys its filters hold.  Each has to read clearly worse than
the program; the bf16 KV control and the store's fail their cell."""
from chipbench import harness, prove
from conftest import smoke_serve_cell, tiny_store_cell


def test_serving_controls_read_worse_than_the_program():
    rows = {r["side"]: r for r in prove.serve_readings(smoke_serve_cell(),
                                                       seed=9, seconds=2.0)}
    prog = rows["program"]
    assert prog["requests"] > 0 and prog["tokens"] > 0
    assert prog["kv_pool_off_dtype"] == 0
    for q in ("int8", "fp8"):
        assert rows[f"control_{q}"]["kv_rel_err"] >= 3 * prog["kv_rel_err"]
    # bfloat16 KV is near the program by the reference's numbers; the
    # pool's type is what fails it
    kv = rows["control_bf16_kv"]
    assert kv["kv_pool_off_dtype"] == 2 and kv["correct"] is False


def test_store_control_is_not_correct():
    row = prove.store_control(tiny_store_cell(), seed=9, seconds=1.0)
    assert row["probe_mismatched_pairs"] > 0 and row["wrong_answers"] > 0
    checks = [{"name": k, "value": row[k], "limit": 0}
              for k in ("probe_mismatched_pairs", "wrong_answers")]
    assert not harness.all_within(checks)
