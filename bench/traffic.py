"""The one traffic generator.  A traffic mix is a data file
(``bench/traffic/<mix>.json``) of the parameters read here:

* ``"loop": "closed"`` -- ``clients`` callers, each with one request
  outstanding, sending the next when the answer comes back.
* ``"loop": "open"`` -- requests on a schedule at ``rate`` per second,
  whatever the answers do.  A window of ``seconds`` holds ``round(seconds
  * rate)`` requests whose gaps are the quantiles of an exponential
  distribution, put in an order drawn from the seed: every seed sends the
  same number of requests with the same set of gaps.
* each request classifies one frame, drawn from a pool of ``pool_frames``
  frames made in set-up.

The calling thread is the one generator thread.  Completion callbacks (run
by the engine's worker) only stamp the time and, in a closed loop, hand the
caller back to the generator.  Latency is timed from when a request was due: in an open
loop its scheduled time, in a closed loop the moment its caller got the
previous answer.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

OK, REFUSED, ERROR, UNANSWERED = "ok", "refused", "error", "unanswered"


class Log:
    """What happened to every request of one window.  Answers are copied
    out of their futures as they arrive, so nothing of a request but its
    numbers outlives it: a window's worth of futures kept alive would make
    every full collection of the garbage collector walk them."""

    def __init__(self):
        self.frames: List[int] = []        # pool index per request
        self.due: List[float] = []
        self.sent: List[float] = []
        self.done: List[Optional[float]] = []
        self.status: List[str] = []
        self.sims: List[Any] = []          # (1, C) per answered request
        self.ids: List[Any] = []
        self.lateness_s = 0.0              # generator's worst lateness
        self._lock = threading.Lock()
        self._pending = 0
        self._closed = False
        self._settled = threading.Event()

    def __len__(self) -> int:
        return len(self.due)

    def add(self, frame: int, due: float) -> int:
        with self._lock:
            self._pending += 1
        self.frames.append(frame)
        self.due.append(due)
        self.done.append(None)
        self.status.append(OK)
        self.sims.append(None)
        self.ids.append(None)
        return len(self.due) - 1

    def complete(self, i: int, fut) -> float:
        """Record request ``i``'s answer (``fut`` done) or its refusal
        (``fut`` None); returns the time it was recorded."""
        t = time.perf_counter()
        if fut is None:
            self.status[i] = REFUSED
        elif fut.cancelled() or fut.exception() is not None:
            self.status[i] = ERROR
        else:
            res = fut.result()
            self.sims[i], self.ids[i] = res.sims, tuple(res.class_ids)
        self.done[i] = t
        with self._lock:
            self._pending -= 1
            if self._closed and self._pending == 0:
                self._settled.set()
        return t

    def settle(self, deadline: float) -> None:
        """Wait until ``deadline`` for every answer; mark what never came."""
        with self._lock:
            self._closed = True
            if self._pending == 0:
                self._settled.set()
        self._settled.wait(max(deadline - time.perf_counter(), 0.0))
        for i, d in enumerate(self.done):
            if d is None:
                self.status[i] = UNANSWERED


def open_schedule(rate: float, seconds: float, rng: np.random.Generator
                  ) -> np.ndarray:
    """Due times (seconds from the window's start) of an open loop."""
    n = int(round(float(rate) * seconds))
    if n == 0:
        return np.zeros(0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps * (seconds / gaps.sum()))
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


class Generator:
    """Drives ``submit(frames) -> Future`` with one traffic mix."""

    def __init__(self, spec: Dict, pool: np.ndarray, seed: int,
                 submit: Callable[[np.ndarray], Any],
                 refused: type = Exception):
        self.spec = spec
        self.pool = pool
        self.rng = np.random.default_rng([int(seed), 7])
        self.submit = submit
        self.refused = refused

    def _send(self, log: Log, due: float, on_done: Callable) -> None:
        frame = int(self.rng.integers(0, len(self.pool)))
        i = log.add(frame, due)
        now = time.perf_counter()
        log.sent.append(now)
        log.lateness_s = max(log.lateness_s, now - due)
        try:
            fut = self.submit(self.pool[frame])
        except self.refused:
            on_done(i, None)
            return
        fut.add_done_callback(lambda f, i=i: on_done(i, f))

    def run(self, t0: float, seconds: float) -> Log:
        if self.spec["loop"] == "closed":
            return self._closed(t0, seconds)
        if self.spec["loop"] == "open":
            return self._open(t0, seconds)
        raise ValueError(f"unknown loop {self.spec['loop']!r}")

    def _closed(self, t0: float, seconds: float) -> Log:
        log = Log()
        ready: "queue.SimpleQueue" = queue.SimpleQueue()
        t_end = t0 + seconds

        def on_done(i, fut):
            ready.put(log.complete(i, fut))

        for _ in range(int(self.spec["clients"])):
            ready.put(t0)
        while True:
            try:
                t_ready = ready.get(
                    timeout=max(t_end - time.perf_counter(), 0.0) + 1.0)
            except queue.Empty:
                t_ready = None
            if time.perf_counter() >= t_end:
                break
            if t_ready is not None:
                self._send(log, t_ready, on_done)
        return log

    def _open(self, t0: float, seconds: float) -> Log:
        log = Log()
        due = open_schedule(self.spec["rate"], seconds, self.rng)

        def on_done(i, fut):
            log.complete(i, fut)

        for d in due:
            t_due = t0 + float(d)
            wait = t_due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self._send(log, t_due, on_done)
        return log
