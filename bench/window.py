"""The measured loop: the loop of ``repro.launch.train``, dispatched ahead.

Each step is ``state, metrics = jstep(state, place_batch(batch(i)))``.  The
host runs at most ``DEPTH`` steps ahead of the device; a waiter thread
blocks, in order, on each step's small ``metrics["step"]`` output and stamps
its completion, so the loop never blocks on a step it dispatched.  Host
spans (``dispatch``, ``wait``, ``drain``) are profiler annotations: a
traced run attributes the device's idle gaps to them.
"""
from __future__ import annotations

import queue
import threading
import time

import jax
from jax.profiler import TraceAnnotation

#: steps the host may run ahead of the last completed one
DEPTH = 2


class Window:
    """``run(state, first, stop)``: steps ``first, first + 1, ...`` until
    ``stop(n_dispatched, seconds_elapsed)``; then waits for the last.  Keeps
    ``start``, ``end`` (host clock) and ``done`` (each step's completion)."""

    def __init__(self, jstep, feed):
        self.jstep, self.feed = jstep, feed
        self.start = self.end = 0.0
        self.done: list = []

    def _wait_all(self, outs, slots):
        while True:
            out = outs.get()
            if out is None:
                return
            out.block_until_ready()
            self.done.append(time.perf_counter())
            slots.release()

    def run(self, state, first: int, stop):
        outs, slots = queue.Queue(), threading.Semaphore(DEPTH)
        waiter = threading.Thread(target=self._wait_all, args=(outs, slots))
        waiter.start()
        n = 0
        try:
            self.start = time.perf_counter()
            while not stop(n, time.perf_counter() - self.start):
                with TraceAnnotation("wait"):
                    slots.acquire()
                with TraceAnnotation("dispatch"):
                    state, metrics = self.jstep(state, self.feed(first + n))
                outs.put(metrics["step"])
                n += 1
        finally:
            outs.put(None)
            with TraceAnnotation("drain"):
                waiter.join()
                jax.block_until_ready(state)
            self.end = time.perf_counter()
        return state, n

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def intervals(self) -> list:
        """Seconds between consecutive completions, the first counted from
        the window's start."""
        marks = [self.start] + self.done
        return [b - a for a, b in zip(marks, marks[1:])]
