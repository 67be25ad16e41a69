"""Traffic generation and the open-loop latency arithmetic."""

import time
from dataclasses import dataclass, field

import numpy as np

import traffic


def test_every_seed_gets_the_same_work():
    mix = {"loop": "open", "rate_rps": 500.0, "request_rows": [1, 1]}
    a = traffic.open_schedule(mix, 10.0, np.random.default_rng(1))
    b = traffic.open_schedule(mix, 10.0, np.random.default_rng(2))
    assert len(a) == len(b) == 5000
    assert a[-1] <= 10.0 and b[-1] <= 10.0
    assert not np.allclose(a, b)
    assert np.allclose(np.sort(np.diff(a, prepend=0)), np.sort(np.diff(b, prepend=0)))
    bulk = {"request_rows": [32, 127]}
    s1 = traffic.sizes(bulk, np.random.default_rng(1), 96 * 3)
    s2 = traffic.sizes(bulk, np.random.default_rng(2), 96 * 3)
    assert sorted(s1) == sorted(s2) and list(s1) != list(s2)


@dataclass
class Req:
    queries: np.ndarray
    enqueued_at: float
    status: str = "queued"
    completed_at: float | None = None
    result: tuple | None = None


@dataclass
class StallingScheduler:
    """Answers each request at once, except that one drain stalls the loop
    for ``stall`` seconds, as a long engine step would."""
    clock: list
    stall_at: int
    stall: float
    max_wait: float = 0.005
    calls: int = 0
    queue: list = field(default_factory=list)

    def submit(self, q):
        r = Req(q, self.clock[0])
        self.queue.append(r)
        return r

    def drain(self, force=True):
        self.calls += 1
        if self.calls == self.stall_at:
            self.clock[0] += self.stall
        done, self.queue = self.queue, []
        for r in done:
            r.status, r.completed_at = "served", self.clock[0]
        return done


def test_a_stall_shows_in_the_tail_of_every_request_due_during_it(monkeypatch):
    now = [0.0]
    monkeypatch.setattr(time, "sleep", lambda s: now.__setitem__(0, now[0] + s))
    mix = {"loop": "open", "rate_rps": 100.0, "request_rows": [1, 1]}
    sched = StallingScheduler(clock=now, stall_at=50, stall=0.3)
    pool = np.zeros((16, 4), np.float32)
    t0, sent = traffic.run_open(sched, pool, mix, 2.0, seed=5, clock=lambda: now[0])
    lat = np.asarray([(s.req.completed_at - t0 - s.due) for s in sent])
    assert len(sent) == 200
    # About 0.3 s x 100 req/s = 30 requests fell due inside the stall, and
    # each waits out the rest of it from its due time, not from submission.
    waited = lat[lat > 0.01]
    assert 25 <= len(waited) <= 35
    assert waited.max() > 0.25
    assert np.percentile(lat, 95) > 0.05
    # Measured from submission instead, the stall vanishes from the tail.
    from_submit = np.asarray([s.req.completed_at - s.req.enqueued_at for s in sent])
    assert np.percentile(from_submit, 95) < 1e-9
