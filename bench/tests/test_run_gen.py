"""A whole run of a toy closed-loop cell on the CPU: the sessions'
first requests are prefilled in set-up, later ones follow completions,
and the served tokens agree with the reference."""
from bench.tests._subproc import run_tiny


def test_toy_closed_loop_run():
    (res,) = run_tiny("tiny.gen", "3", "987654321987", "none")
    assert res["correct"] is True, res["checked"]
    assert set(res["metrics"]) == {"output_tokens_per_s", "setup_s",
                                   "requests_served"}
    # 4 sessions of 3 requests of at most 30 tokens finish in 3 s on the
    # CPU, so completions did submit follow-up requests
    assert res["attempted"] > 4
