"""tools/windows.py: the repeated window keeps run.py's rules."""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))


class FakeClock:
    def mark(self):
        return (0.0, 0)


def test_back_to_back_windows_count_every_step_sent_and_stop_once():
    import windows

    rows, stops = [], []
    hook_cls = windows.windows_hook(3, rows.append)
    hook = hook_cls(seconds=0.05, warm_steps=4, check_steps=0,
                    clock=FakeClock(), trace_dir=None, batch_images=8,
                    stop=lambda: stops.append(1))

    def step_fn(state, batch, rng):
        time.sleep(0.004)
        return state + 1, {"loss": 1.0}

    step = hook.wrap(step_fn)
    state, calls = 0, 0
    while not stops and calls < 1000:
        state, _aux = step(state, None, None)
        calls += 1
    for _ in range(3):  # the loop runs on until it sees its guard
        state, _aux = step(state, None, None)
    assert stops == [1] and hook.done
    assert [r["window"] for r in rows] == [0, 1, 2]
    # steps 1..3 warm, step 4 starts the clock, every later call belongs
    # to exactly one window
    assert 4 + sum(r["steps"] for r in rows) == calls == state - 3
    for r in rows:
        assert r["window_s"] >= 0.05
        assert r["img_per_s"] == r["steps"] * 8 / r["window_s"]
        assert r["compiles_so_far"] == 0
    assert hook.window_steps == sum(r["steps"] for r in rows)
