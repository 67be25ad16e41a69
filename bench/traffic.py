"""Traffic: one general generator and the two load loops.

A traffic mix is a JSON file under ``bench/traffic/``:

  {"loop": "closed", "clients": 8, "request_rows": [32, 127]}
  {"loop": "open", "rate_rps": 950.0, "request_rows": [1, 1]}

``closed``: each client keeps one request outstanding and sends its next as
soon as its answer is back.  ``open``: requests fall due on a schedule at
``rate_rps`` whether or not earlier ones are answered.  Every seed gets the
same work in another order: request sizes cycle through every whole number
in ``request_rows`` once per cycle, and open-loop gaps are the quantiles of
an exponential distribution at the rate, shuffled, so a window holds the
same number of requests and rows for every seed.  Which pool queries a
request carries is drawn from the seed.

Latency runs from the moment a request was due to the moment its answer is
in host memory, so a stall in the loop shows in the tail of every request
that fell due during it.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import numpy as np


@dataclass
class Sent:
    due: float  # seconds after the window opened
    rows: np.ndarray  # pool indices of the request's queries
    req: object = None  # the scheduler's Request once submitted


def sizes(mix: dict, rng: np.random.Generator, count: int) -> np.ndarray:
    lo, hi = mix["request_rows"]
    cycle = np.arange(lo, hi + 1)
    reps = -(-count // len(cycle))
    return np.concatenate([rng.permutation(cycle) for _ in range(reps)])[:count]


def open_schedule(mix: dict, seconds: float, rng) -> np.ndarray:
    """Due times in [0, seconds): round(rate * seconds) arrivals whose gaps
    are exponential quantiles at the rate, in a seeded order."""
    rate = float(mix["rate_rps"])
    m = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-(np.arange(m) + 0.5) / m) / rate
    due = np.cumsum(rng.permutation(gaps))
    return due * (seconds / max(due[-1], seconds))  # keep the last one inside


def _pool_rows(rng, pool_size, n):
    start = int(rng.integers(pool_size))
    return (start + np.arange(n)) % pool_size


def run_open(sched, pool: np.ndarray, mix: dict, seconds: float, seed: int,
             clock=time.perf_counter):
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    due = open_schedule(mix, seconds, rng)
    n = sizes(mix, rng, len(due))
    sent = [Sent(float(t), _pool_rows(rng, len(pool), int(s)))
            for t, s in zip(due, n)]
    t0 = clock()
    i = head = 0  # next request to submit; oldest one not yet answered
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            now = clock() - t0
            with jax.profiler.TraceAnnotation("bench.submit"):
                while i < len(sent) and sent[i].due <= now:
                    sent[i].req = sched.submit(pool[sent[i].rows])
                    i += 1
            with jax.profiler.TraceAnnotation("bench.drain"):
                done = sched.drain(force=i == len(sent))
            while head < i and sent[head].req.status != "queued":
                head += 1
            if i == len(sent) and head == i:
                break
            if done:
                continue
            # Nothing finished: sleep to the next due time or the
            # scheduler's flush of the oldest waiting request.
            wake = sent[i].due if i < len(sent) else now
            if head < i:
                wake = min(wake, sent[head].req.enqueued_at - t0
                           + sched.max_wait)
            pause = wake - (clock() - t0)
            if pause > 0:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(pause)
    return t0, sent


def run_closed(sched, pool: np.ndarray, mix: dict, seconds: float, seed: int,
               clock=time.perf_counter):
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    clients = int(mix["clients"])
    n = iter(sizes(mix, rng, 1 << 20))
    sent: list[Sent] = []
    outstanding: dict[int, Sent] = {}
    t0 = clock()

    def send(c):
        s = Sent(clock() - t0, _pool_rows(rng, len(pool), int(next(n))))
        with jax.profiler.TraceAnnotation("bench.submit"):
            s.req = sched.submit(pool[s.rows])
        sent.append(s)
        outstanding[c] = s

    with jax.profiler.TraceAnnotation("bench.window"):
        for c in range(clients):
            send(c)
        while outstanding:
            closing = clock() - t0 >= seconds
            with jax.profiler.TraceAnnotation("bench.drain"):
                done = {id(r) for r in sched.drain(force=closing)}
            for c, s in list(outstanding.items()):
                if id(s.req) in done or s.req.status != "queued":
                    del outstanding[c]
                    if not closing:
                        send(c)
            if not done and not closing:
                oldest = min(s.req.enqueued_at for s in outstanding.values())
                pause = oldest + sched.max_wait - clock()
                if pause > 0:
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        time.sleep(pause)
    return t0, sent
