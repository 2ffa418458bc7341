"""The closed-loop generator keeps exactly N requests outstanding."""
import threading
import time

import numpy as np
import pytest

from bench import harness

closed = harness.load_module(harness.os.path.join(harness.BENCH_DIR, "gen",
                                                  "closed.py"))


class _Future:
    def __init__(self):
        self._ev = threading.Event()
        self.complete_time = None
        self.value = None

    def set(self, value):
        self.value = value
        self.complete_time = time.perf_counter()
        self._ev.set()

    def done(self):
        return self._ev.is_set()

    def result(self, timeout=None):
        if not self._ev.wait(timeout):
            raise TimeoutError
        return self.value


class StubTier:
    """Completes queued requests in buckets of up to 8 from a thread,
    recording how many were in flight at every submit."""

    def __init__(self, service_s=0.002):
        self.lock = threading.Lock()
        self.queue = []
        self.submitted = 0
        self.completed = 0
        self.peaks = []
        self.stop = threading.Event()
        self.service_s = service_s
        self.thread = threading.Thread(target=self._loop, daemon=True)
        self.thread.start()

    def submit(self, image):
        fut = _Future()
        with self.lock:
            self.submitted += 1
            self.peaks.append(self.submitted - self.completed)
            self.queue.append((image, fut))
        return fut

    def _loop(self):
        while not self.stop.is_set():
            with self.lock:
                take, self.queue = self.queue[:8], self.queue[8:]
            time.sleep(self.service_s)
            for image, fut in take:
                fut.set(float(image[0]))
                with self.lock:
                    self.completed += 1


@pytest.mark.parametrize("n", [1, 5, 64])
def test_closed_loop_keeps_n_outstanding(n):
    tier = StubTier()
    pool = np.arange(16, dtype=np.float32)[:, None]
    traffic = {"outstanding": n, "pool": 16, "ramp_s": 0.05}
    try:
        sent, t0, t1 = closed.run(tier.submit, pool, traffic,
                                  np.random.default_rng(3), 0.3)
    finally:
        tier.stop.set()
        tier.thread.join(5)
    assert max(tier.peaks) == n                   # never more than N
    assert tier.peaks[n - 1] == n                 # N sent at once
    assert t1 - t0 == pytest.approx(0.3, abs=0.05)
    assert all(s.ok for s in sent)
    # every request carried the pool image it records
    assert all(s.future.value == float(s.pool_idx) for s in sent)
    # every completion before the close was replaced, except those of the
    # last bucket (up to 8) that may finish as the window closes
    before_close = sum(1 for s in sent if s.t_done < t1)
    assert before_close - 8 <= len(sent) - n <= before_close


def test_closed_loop_bucket_set():
    assert closed.buckets({"outstanding": 1}, 32, 1) == [1]
    assert closed.buckets({"outstanding": 64}, 32, 1) == [1, 2, 4, 8, 16, 32]
    assert closed.buckets({"outstanding": 5}, 32, 1) == [1, 2, 4, 8]
