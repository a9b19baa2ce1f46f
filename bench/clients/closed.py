"""Closed loop: ``in_flight`` requests outstanding, the next one sent as
soon as the oldest has its answer on the host.  One thread; with
``in_flight > 1`` the device queue holds the others (JAX dispatch is
asynchronous)."""
from __future__ import annotations

import time
from collections import deque

from jax.profiler import TraceAnnotation


def run(server, frames, picks, traffic: dict, seconds: float):
    """Serve until ``seconds`` have passed, then drain.  ``picks`` yields
    pool indices.  Returns one record per request, in order:
    ``(pick, t_submit, t_invoked, t_done, answer, handle)``."""
    depth = int(traffic["in_flight"])
    done, queue = [], deque()
    t_end = time.perf_counter() + seconds

    def finish():
        pick, t0, t1, h = queue.popleft()
        with TraceAnnotation("wait"):
            server.wait(h)
        with TraceAnnotation("fetch"):
            answer = server.fetch(h)
        done.append((pick, t0, t1, time.perf_counter(), answer, h))

    while True:
        while len(queue) < depth:
            t0 = time.perf_counter()
            if t0 >= t_end:
                break
            with TraceAnnotation("pick"):
                i = next(picks)
                frame = frames[i]
            with TraceAnnotation("invoke"):
                h = server.invoke(frame)
            queue.append((i, t0, time.perf_counter(), h))
        if not queue:
            return done
        finish()
