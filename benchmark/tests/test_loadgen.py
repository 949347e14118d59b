"""The traffic generator: one seed, one traffic; another seed, the same
set of sizes in another order; open-loop requests timed from their due
time; failures count as the worst latency."""

import concurrent.futures as cf
import threading
import time

import numpy as np
import pytest

from harness import loadgen

MIX = {"loop": "closed", "clients": 3, "pool": 6,
       "sizes": [[12, 16], [16, 12], [8, 10]]}


def test_one_seed_offers_byte_identical_images():
    a, b = loadgen.make_pool(MIX, 7), loadgen.make_pool(MIX, 7)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert a[0].dtype == np.float32


def test_another_seed_offers_the_same_sizes_in_another_order():
    seen = [tuple(im.shape for im in loadgen.make_pool(MIX, s))
            for s in range(8)]
    assert len(set(seen)) > 1, "the order never changed over 8 seeds"
    for shapes in seen:
        assert sorted(shapes) == sorted(seen[0])
    a, b = loadgen.make_pool(MIX, 1), loadgen.make_pool(MIX, 2)
    assert not all(x.shape == y.shape and np.array_equal(x, y)
                   for x, y in zip(a, b))


def test_large_seeds_are_taken():
    loadgen.make_pool(MIX, 2**31 + 12345)
    loadgen.arrivals(dict(MIX, rate=10.0), 2**31 + 12345, 1.0)


def test_pool_must_hold_the_sizes_in_equal_shares():
    with pytest.raises(ValueError):
        loadgen.make_pool(dict(MIX, pool=7), 0)


def test_poisson_arrivals_are_seeded_and_keep_their_rate():
    mix = dict(MIX, loop="open", rate=200.0)
    a, b = loadgen.arrivals(mix, 3, 10.0), loadgen.arrivals(mix, 3, 10.0)
    assert np.array_equal(a, b) and np.all(np.diff(a) > 0) and a[-1] < 10.0
    assert abs(len(a) - 2000) < 200
    assert not np.array_equal(a, loadgen.arrivals(mix, 4, 10.0))


def test_bursts_keep_the_mean_rate_and_crowd_the_on_second():
    mix = dict(MIX, loop="open", rate=100.0,
               burst={"factor": 4, "on_s": 1, "every_s": 5})
    t = loadgen.arrivals(mix, 5, 50.0)
    assert abs(len(t) - 5000) < 400
    on = np.sum((t % 5.0) < 1.0)
    assert on / len(t) == pytest.approx(0.5, abs=0.05)  # 4/(4+4)


class FakeEngine:
    """Answers after ``service_s`` on one worker; optionally refuses."""

    def __init__(self, service_s=0.005, fail_every=0):
        self.pool = cf.ThreadPoolExecutor(1)
        self.service_s, self.fail_every, self.n = service_s, fail_every, 0
        self.lock = threading.Lock()

    def submit(self, im):
        with self.lock:
            self.n += 1
            n = self.n
        if self.fail_every and n % self.fail_every == 0:
            raise RuntimeError("refused")

        def work():
            time.sleep(self.service_s)
            return [None, np.zeros((1, 5), np.float32)]

        return self.pool.submit(work)


def test_closed_loop_runs_for_the_window_and_awaits_every_reply():
    eng = FakeEngine()
    pool = loadgen.make_pool(MIX, 0)
    recs, t0, t_end = loadgen.drive(eng.submit, pool, MIX, 0.3, 0)
    assert [r["i"] for r in recs] == list(range(len(recs)))
    assert all(r["outcome"] == "ok" for r in recs)
    assert t_end - t0 >= 0.3 and t_end == max(r["t_done"] for r in recs)
    # one worker at 5 ms a request: about 60 requests, never more
    assert 20 <= len(recs) <= 0.3 / 0.005 + 4


def test_open_loop_times_each_request_from_when_it_was_due():
    """One worker at 20 ms against 100 req/s: the queue grows, and the
    latency from DUE time grows with it although each submit is prompt."""
    eng = FakeEngine(service_s=0.02)
    mix = dict(MIX, loop="open", rate=100.0, clients=2)
    pool = loadgen.make_pool(mix, 0)
    recs, t0, t_end = loadgen.drive(eng.submit, pool, mix, 0.4, 0)
    due = loadgen.arrivals(mix, 0, 0.4)
    assert len(recs) == len(due)
    for r, d in zip(recs, due):
        assert r["t_due"] == pytest.approx(t0 + d)
    from_due = [(r["t_done"] - r["t_due"]) for r in recs]
    from_submit = [(r["t_done"] - r["t_submit"]) for r in recs]
    assert max(from_due) > 2 * max(from_submit)  # the wait shows
    assert loadgen.lateness_ms(recs) > 50.0       # and the generator says so


def test_a_failed_request_counts_as_the_worst_latency():
    eng = FakeEngine(fail_every=4)
    pool = loadgen.make_pool(MIX, 0)
    recs, t0, t_end = loadgen.drive(eng.submit, pool, MIX, 0.2, 0)
    bad = [r for r in recs if r["outcome"] != "ok"]
    assert bad and all(r["outcome"] == "RuntimeError" for r in bad)
    lat = loadgen.latencies_ms(recs, t0, t_end)
    assert len(lat) == len(recs)
    assert sorted(lat)[-len(bad):] == [max(lat)] * len(bad)
    assert max(lat) >= (t_end - t0) * 1e3
