"""The traffic generators: a seed fixes the schedule, every seed offers
the same sizes and gaps in another order, and the stated medians and
clips hold."""
import statistics

import pytest

from bench import cell as cells

SEEDS = (0, 7, 2**31 + 5, 4_000_000_019)


def _mix(name):
    return json_load(cells.BENCH / "traffic" / f"{name}.json")


def json_load(path):
    import json
    return json.loads(path.read_text())


def _make(name, seed, seconds=51.0):
    mix = _mix(name)
    gen = cells.load_module("generators", mix["kind"])
    src = gen.make(mix, seed, seconds, 151936)
    first = src.initial()
    # a closed loop's later requests wait in their sessions' queues
    later = [r for q in getattr(src, "_queues", []) for r in q]
    return mix, src, (first or list(src.requests)) + later


def _sig(reqs):
    return [(len(r.prompt), r.max_new, round(r.due, 9), r.prompt[:4])
            for r in reqs]


@pytest.mark.parametrize("name", ["chat_short", "long_gen"])
def test_same_seed_same_schedule(name):
    assert _sig(_make(name, SEEDS[2])[2]) == _sig(_make(name, SEEDS[2])[2])
    assert _sig(_make(name, 1)[2]) != _sig(_make(name, 2)[2])


@pytest.mark.parametrize("name", ["chat_short", "long_gen"])
def test_every_seed_same_work(name):
    def work(seed):
        _, _, reqs = _make(name, seed)
        return (sorted(len(r.prompt) for r in reqs),
                sorted(r.max_new for r in reqs))
    first = work(SEEDS[0])
    for s in SEEDS[1:]:
        assert work(s) == first


def test_open_loop_medians_clips_and_arrivals():
    mix, src, reqs = _make("chat_short", SEEDS[3], seconds=2000.0)
    p = [len(r.prompt) for r in reqs]
    o = [r.max_new for r in reqs]
    assert min(p) >= 16 and max(p) <= 256
    assert min(o) >= 16 and max(o) <= 384
    assert abs(statistics.median(p) - 48) <= 1
    assert abs(statistics.median(o) - 96) <= 1
    dues = [r.due for r in reqs]
    assert dues == sorted(dues) and dues[-1] < 2000.0
    assert len(reqs) == round(mix["rate_rps"] * 2000.0)
    assert all(1 <= t < 151936 for r in reqs for t in r.prompt)


def test_closed_loop_sessions():
    mix, src, reqs = _make("long_gen", SEEDS[1])
    first = [r for r in reqs if r.in_setup]
    assert len(first) == mix["sessions"]
    assert {r.session for r in first} == set(range(mix["sessions"]))
    for r in reqs:
        assert 16 <= len(r.prompt) <= 32
        assert 1536 <= r.max_new <= 2048
        assert len(r.prompt) + r.max_new - 1 <= 2080
    # a finished request makes its session's next one due at once
    src.finished(first[0], 12.5)
    (nxt,) = src.due(12.5)
    assert nxt.session == first[0].session and nxt.due == 12.5
