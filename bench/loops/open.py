"""Open loop: requests fall due at ``traffic["rate_per_s"]`` whatever
the backlog, as many users who do not wait for each other send them.
Each is sent on its own thread at its due time and timed from that due
time, so a late send counts in its latency.

``arrivals`` is ``"uniform"`` (one every 1/rate s) or ``"poisson"``:
the gaps are the exponential distribution's quantiles at (k + 0.5)/n
for the n = ceil(rate x seconds) arrivals of the window, in an order
drawn from the run's seed, so every seed offers the same gaps and the
same load.  All n are sent; the window ends when the last is back.  The
warm-up (``count``) is paced uniformly."""
from __future__ import annotations

import math
import threading

import numpy as np

import generator


def gaps(rate: float, n: int, arrivals: str, order_seed: int
         ) -> np.ndarray:
    """``n`` gaps between due times, in seconds."""
    if arrivals == "uniform":
        return np.full(n, 1.0 / rate)
    if arrivals != "poisson":
        raise ValueError(f"unknown arrivals {arrivals!r}")
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q) / rate
    return np.random.default_rng(order_seed).permutation(g)


def drive(client, source, traffic: dict, *, seconds: float | None = None,
          count: int | None = None):
    rate = float(traffic["rate_per_s"])
    if count is not None:
        steps = gaps(rate, count, "uniform", 0)
    else:
        n = max(1, math.ceil(rate * seconds))
        steps = gaps(rate, n, traffic.get("arrivals", "uniform"),
                     source.order_seed)
    due = np.concatenate([[0.0], np.cumsum(steps)[:-1]])
    lock = threading.Lock()
    done: list[generator.Request] = []
    threads = []
    t0 = generator.clock()

    def one(t_due: float):
        req = generator.send(client, source, traffic, t_due=t_due)
        with lock:
            done.append(req)

    for d in due:
        lag = t0 + d - generator.clock()
        if lag > 0:
            threading.Event().wait(lag)
        t = threading.Thread(target=one, args=(t0 + d,), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    return done, t0, generator.clock()
