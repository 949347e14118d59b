"""The one general traffic generator for serve cells.  A mix is a data
file (``traffic/<mix>.json``); everything random in it - which image a
request carries, when an open-loop request is due - is drawn from the
seed before any thread starts, so one seed offers identical traffic and
two seeds offer the same set of sizes and arrivals in another order.

Modelled on ``mx_rcnn_tpu/serve/loadgen.py::run_load`` (closed loop;
``arrivals`` for open loop), with three differences a benchmark needs: the
window is bounded by time, not by a count; the images are made before the
window, not inside it; and an open-loop request is timed from the moment
it was DUE, not from when a late client submitted it.

Parameters of a mix (``kind: serve``):
  loop      "closed" (``clients`` callers, each waits for its reply) or
            "open" (``rate`` requests a second, Poisson arrivals)
  sizes     [[h, w], ...] original image sizes, used in equal shares
  pool      number of distinct images made from the seed (a multiple of
            the number of sizes); request i carries image order[i % pool]
  burst     open loop only: {"factor": 4, "on_s": 1, "every_s": 5} raises
            the rate by ``factor`` for ``on_s`` of every ``every_s``, the
            mean rate kept
  clients   closed loop: callers; open loop: worker threads (a cap on the
            requests in flight)
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Dict, List, Sequence, Tuple

import numpy as np


def make_pool(traffic: Dict[str, Any], seed: int) -> List[np.ndarray]:
    """``pool`` float32 RGB noise images, the sizes in equal shares, content
    and order from the seed."""
    sizes = [tuple(s) for s in traffic["sizes"]]
    n = int(traffic["pool"])
    if n % len(sizes):
        raise ValueError(f"pool {n} is no multiple of {len(sizes)} sizes")
    rng = np.random.RandomState(seed % (2**31 - 1))
    shapes = [sizes[i % len(sizes)] for i in range(n)]
    order = rng.permutation(n)
    return [
        rng.randint(0, 256, shapes[j] + (3,)).astype(np.float32)
        for j in order
    ]


def arrivals(traffic: Dict[str, Any], seed: int, seconds: float) -> np.ndarray:
    """Due offsets (s) of an open-loop mix over ``seconds``: a Poisson
    process at ``rate``, optionally in bursts of the same mean."""
    rate = float(traffic["rate"])
    rng = np.random.RandomState((seed + 7919) % (2**31 - 1))
    burst = traffic.get("burst")
    if not burst:
        n = int(rate * seconds * 1.5) + 16
        t = np.cumsum(rng.exponential(1.0 / rate, n))
        return t[t < seconds]
    f, on, every = (float(burst[k]) for k in ("factor", "on_s", "every_s"))
    # rate_on = f * rate_off, and the mean over a period is ``rate``
    off_rate = rate * every / (f * on + (every - on))
    out, t = [], 0.0
    while t < seconds:
        in_burst = (t % every) < on
        t += rng.exponential(1.0 / (off_rate * f if in_burst else off_rate))
        if t < seconds:
            out.append(t)
    return np.asarray(out)


class Record(dict):
    """One request: ``i``, ``image`` (index into the pool), ``t_due``,
    ``t_submit``, ``t_done``, ``outcome`` ("ok" or the failure's kind),
    ``dets`` (the reply)."""


def _no_span(_name):
    return contextlib.nullcontext()


def drive(submit: Callable[[np.ndarray], Any], pool: Sequence[np.ndarray],
          traffic: Dict[str, Any], seconds: float, seed: int,
          refused: Tuple[type, ...] = (),
          backoff_s: float = 0.002, give_up: int = 2000,
          span: Callable[[str], Any] = _no_span):
    """Offer the mix for ``seconds``; wait for every reply.  → (records,
    t0, t_end): the window runs from the first offer to the last reply, so
    a rate over it is all the work over all the time.  ``refused``: the
    exception types that mean back-pressure (retried after ``backoff_s``,
    ``give_up`` times at most; the request then counts as failed).
    ``span(name)`` is a context manager the traced run hands in, so that
    the trace shows what each client was doing."""
    loop = traffic["loop"]
    clients = int(traffic["clients"])
    due = arrivals(traffic, seed, seconds) if loop == "open" else None
    lock = threading.Lock()
    records: List[Record] = []
    counter = iter(range(10**9))

    def one(i: int, t_due: float) -> None:
        rec = Record(i=i, image=i % len(pool), t_due=t_due)
        rec["t_submit"] = time.monotonic()
        fut, tries = None, 0
        while fut is None:
            try:
                with span("bench.submit"):
                    fut = submit(pool[rec["image"]])
            except refused:
                tries += 1
                if tries >= give_up:
                    rec["outcome"] = "refused"
                    break
                time.sleep(backoff_s)
            except Exception as e:  # synchronous reject
                rec["outcome"] = type(e).__name__
                break
        if fut is not None:
            try:
                with span("bench.wait_reply"):
                    rec["dets"] = fut.result(timeout=120.0)
                rec["outcome"] = "ok"
            except Exception as e:
                rec["outcome"] = type(e).__name__
        rec["t_done"] = time.monotonic()
        with lock:
            records.append(rec)

    def closed_client(t0: float) -> None:
        while time.monotonic() - t0 < seconds:
            with lock:
                i = next(counter)
            one(i, time.monotonic())

    def open_client(t0: float) -> None:
        while True:
            with lock:
                i = next(counter)
            if i >= len(due):
                return
            wait = t0 + due[i] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            one(i, t0 + float(due[i]))  # timed from when it was due

    t0 = time.monotonic()
    target = closed_client if loop == "closed" else open_client
    threads = [
        threading.Thread(target=target, args=(t0,), name=f"bench-client-{c}",
                         daemon=True)
        for c in range(max(1, clients))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t_end = max([r["t_done"] for r in records], default=time.monotonic())
    records.sort(key=lambda r: r["i"])
    return records, t0, t_end


def latencies_ms(records: Sequence[Record], t0: float, t_end: float) -> List[float]:
    """Every request's latency from its due time; one that failed or was
    refused counts as the worst (the longest seen, or the whole window)."""
    ok = [(r["t_done"] - r["t_due"]) * 1e3 for r in records
          if r["outcome"] == "ok"]
    worst = max(ok + [(t_end - t0) * 1e3])
    return ok + [worst] * (len(records) - len(ok))


def lateness_ms(records: Sequence[Record]) -> float:
    """How late the generator ran: the longest submit after due."""
    return max(((r["t_submit"] - r["t_due"]) * 1e3 for r in records),
               default=0.0)
