"""Closed loop: ``traffic["clients"]`` clients, each of which sends a
request, waits for its result and sends the next, as a user does who
looks at each answer before asking again."""
from __future__ import annotations

import threading

import generator


def drive(client, source, traffic: dict, *, seconds: float | None = None,
          count: int | None = None):
    lock = threading.Lock()
    done: list[generator.Request] = []
    claimed = [0]
    t0 = generator.clock()

    def more() -> bool:
        # claims one request of the budget, or says it is spent
        with lock:
            if count is not None and claimed[0] >= count:
                return False
            if seconds is not None and generator.clock() - t0 >= seconds:
                return False
            claimed[0] += 1
            return True

    def client_loop():
        while more():
            req = generator.send(client, source, traffic)
            with lock:
                done.append(req)

    threads = [threading.Thread(target=client_loop, daemon=True)
               for _ in range(traffic["clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return done, t0, generator.clock()
