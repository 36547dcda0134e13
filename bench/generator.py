"""The one traffic generator: requests made from a mix's data, sent by
the loop the mix names.

A traffic mix (``traffic/<mix>.json``) is data.  ``loop`` names the
module ``loops/<loop>.py`` that paces the requests (a function
``drive(client, source, traffic, *, seconds, count)``); the rest are
that loop's parameters (clients, arrival rate, poll interval), how many
requests warm up, how long a request may take, and optionally
``variants``: parameter overrides, ``{"<plugin>.<param>": value}``,
that the requests take in turn.  Every request gets the next seed of
one stream drawn from the run's seed, and is timed on the client until
the result's bytes are in hand.
"""
from __future__ import annotations

import dataclasses
import json
import threading
import time
from typing import Iterator

import numpy as np

import jax

import metric_lib

clock = time.perf_counter


@dataclasses.dataclass
class Request:
    seed: int
    spec: dict
    t_submit: float                     # due time: when the user sent it
    t_sent: float | None = None         # when the loop got it out
    t_done: float | None = None
    result_s: float | None = None       # time in GET /jobs/{id}/result
    error: str | None = None
    volume: np.ndarray | None = None

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit


def seed_stream(seed: int, stream: int) -> Iterator[int]:
    """Request seeds (non-negative, below 2**31) drawn from the run's
    seed; ``stream`` separates warm-up from the window."""
    rng = np.random.default_rng([seed % 2**64, stream])
    while True:
        yield int(rng.integers(0, 2**31))


def spec_for(config: dict, seed: int, overrides: dict | None = None
             ) -> dict:
    """The configuration's process list with the loader's seed set and
    ``overrides`` (``{"<plugin>.<param>": value}``) applied."""
    spec = json.loads(json.dumps(config["process_list"]))
    by_name = {e["plugin"]: e for e in spec["plugins"]}
    by_name["synthetic_tomo_loader"]["params"]["seed"] = seed
    for key, value in (overrides or {}).items():
        plugin, param = key.split(".", 1)
        if plugin not in by_name:
            raise KeyError(f"variant {key!r}: the chain has no {plugin!r}")
        by_name[plugin].setdefault("params", {})[param] = value
    return spec


class Source:
    """The requests of one stream, in order: the i-th has the stream's
    i-th seed and the mix's i-th variant (cycled).  Safe to take from
    several client threads."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 stream: int, prefix: str):
        self._config = config
        self._variants = traffic.get("variants") or [{}]
        self._seeds = seed_stream(seed, stream)
        self._prefix = prefix
        self._lock = threading.Lock()
        self.taken = 0
        #: seeds a loop's own draws (such as the order of arrivals)
        self.order_seed = [seed % 2**64, stream, 1]

    def take(self) -> tuple[str, int, dict]:
        """(job id, seed, process list) of the next request."""
        with self._lock:
            i, seed = self.taken, next(self._seeds)
            self.taken += 1
        variant = self._variants[i % len(self._variants)]
        return (f"{self._prefix}-{i + 1}", seed,
                spec_for(self._config, seed, variant))


def send(client, source: Source, traffic: dict,
         t_due: float | None = None) -> Request:
    """The source's next request, timed from ``t_due`` (default: now)
    until the result is in hand.  A failed or refused request comes back
    with ``error`` set."""
    from repro.service import ServiceError
    job_id, seed, spec = source.take()
    now = clock()
    req = Request(seed=seed, spec=spec,
                  t_submit=now if t_due is None else t_due, t_sent=now)
    try:
        with jax.profiler.TraceAnnotation("client.submit"):
            jid = client.submit(spec, job_id=job_id)
        with jax.profiler.TraceAnnotation("client.wait"):
            snap = client.wait(jid, timeout=traffic["request_timeout_s"],
                               poll=traffic["poll_s"])
        if snap["state"] != "done":
            req.error = f"job {jid} {snap['state']}: {snap.get('error')}"
            return req
        t = clock()
        with jax.profiler.TraceAnnotation("client.result"):
            req.volume = client.result(jid)
        req.t_done = clock()
        req.result_s = req.t_done - t
    except (ServiceError, TimeoutError, OSError) as e:
        req.error = f"{type(e).__name__}: {e}"
    return req


def drive(client, source: Source, traffic: dict, *,
          seconds: float | None = None, count: int | None = None
          ) -> tuple[list[Request], float, float]:
    """Send the source's requests with the loop the mix names, until
    ``count`` have been sent, or until ``seconds`` have passed and every
    request due before then has come back.  Returns the requests in
    the order they were due and the window's (start, end) on
    :func:`clock`."""
    loop = metric_lib.load("loops", traffic["loop"])
    requests, t0, t1 = loop.drive(client, source, traffic,
                                  seconds=seconds, count=count)
    return sorted(requests, key=lambda r: r.t_submit), t0, t1
