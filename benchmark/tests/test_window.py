"""The window rule: no pass is cut, the pass in progress is finished,
everything between the first start and the last end is counted."""

from benchmark import harness


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def window(seconds, durations, gap=0.0):
    clock = Clock()
    todo = iter(durations)

    def cycle(i):
        clock.now += next(todo)
        return {"files": 10, "i": i}

    w = harness.run_window(seconds, cycle, clock=clock)
    return w


def test_a_pass_starts_only_while_the_window_is_open():
    w = window(40, [19, 19, 19, 19])
    assert [p["i"] for p in w["passes"]] == [0, 1, 2]   # starts at 0, 19, 38
    assert w["window_s"] == 57                          # the third is finished
    w = window(40, [20, 20, 20])
    assert len(w["passes"]) == 2 and w["window_s"] == 40


def test_the_pass_in_progress_is_always_finished():
    w = window(10, [75])
    assert len(w["passes"]) == 1 and w["window_s"] == 75


def test_everything_between_first_start_and_last_end_is_counted():
    w = window(40, [15, 15, 15])
    assert w["passes"][0]["start_s"] == 0
    assert [p["end_s"] for p in w["passes"]] == [15, 30, 45]
    assert w["window_s"] == sum(p["cycle_s"] for p in w["passes"])
    assert w["closed"] - w["opened"] == w["window_s"]
    # the rate is all files over the whole window
    assert sum(p["files"] for p in w["passes"]) / w["window_s"] == 30 / 45
