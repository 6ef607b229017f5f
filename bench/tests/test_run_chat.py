"""A whole run of a toy open-loop cell on the CPU, past the harness's
look for a chip: it is correct, reports its end-to-end metrics (one of
them added as a new file), and the precision-stepped control, judged by
the same limits, comes out not correct."""
import json

from bench.tests._subproc import run_tiny
from bench.tests.tiny import DATA


def test_toy_chat_run_and_control():
    (res,) = run_tiny("tiny.chat", "3", "3141592653589", "control")
    assert res["correct"] is True, res["checked"]
    assert set(res["metrics"]) == {"output_tokens_per_s", "ttft_p50_s",
                                   "setup_s", "requests_served"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["checked"]["compiles_in_window"]["value"] == 0
    assert list(res)[-1] == "checked"
    limit = json.loads((DATA / "tiny.json").read_text())["check"]
    assert res["checked"]["logit_gap"]["value"] <= limit["logit_gap"]
    control = res["control"]
    assert control["correct"] is False, control["checked"]
    assert control["checked"]["logit_gap"]["value"] > limit["logit_gap"]
