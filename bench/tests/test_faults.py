"""With the timed path broken underneath, a run of the toy cell comes out
not correct: once for each fault the cells can have. The three runs
share one child process (and its compilation cache)."""
import pytest

from bench.tests._subproc import run_tiny

FAULTS = (("decode_state_unchanged", "11"), ("token_altered", "12"),
          ("half_batch_left_out", "13"))


@pytest.fixture(scope="module")
def results():
    args = [a for fault, seed in FAULTS
            for a in ("tiny.chat", "2", seed, fault)]
    return {r["fault"]: r for r in run_tiny(*args)}


def _not_correct(res):
    assert res["correct"] is False
    assert res["checked"]["compiles_in_window"]["value"] == 0
    gap = res["checked"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_decode_state_unchanged_is_not_correct(results):
    """A decode step that returns its cache unchanged."""
    _not_correct(results["decode_state_unchanged"])


def test_token_altered_is_not_correct(results):
    """Every served token altered where it is chosen."""
    _not_correct(results["token_altered"])


def test_half_batch_is_not_correct(results):
    """Half of the batch served another half's logits."""
    _not_correct(results["half_batch_left_out"])
