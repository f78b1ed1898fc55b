"""The harness end to end on the CPU, at a CPU size: the timed path as
it is passes its check, and the control (the reference on float8
weights), held to the same limits by the same check, fails it. Closed
and open loop."""
from chipbench.tests import rehearsal


def test_dense_run_is_correct_and_its_control_is_not():
    r = rehearsal.run(control=True)
    assert r["correct"], r["checks"]
    assert r["readings"]["requests"] > 4 and r["readings"]["picks"] > 0
    assert {"decode_tok_s", "itl_p95_ms", "ttft_p50_ms", "setup_s"} <= \
        set(r["metrics"])
    assert r["control_correct"] is False, r["control"]
    assert list(r)[-1] == "checks"


def test_open_loop_run():
    r = rehearsal.run(mix=rehearsal.OPEN, seconds=1.0)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 4
