"""Sustained-load proof for the service layer: an OPEN-LOOP harness.

Closed-loop benchmarks (submit, wait, submit...) let a slow server set
its own pace and hide queueing collapse.  This harness submits a mixed
stream of solo jobs and parameter sweeps at a FIXED arrival rate
against a broker-mode service with N worker subprocesses, regardless
of how the backlog looks — then reports what the paper's service story
must sustain:

* throughput (completed jobs/s over the busy interval),
* client-observed end-to-end latency p50/p99 (``finished_at -
  submitted_at`` from job snapshots — includes queueing),
* the queue-depth time series sampled from ``GET /stats`` (the
  open-loop tell: a stable system plateaus, an overloaded one grows
  without bound),
* lease expiries + requeues (zero under healthy load),

and writes ``BENCH_service.json``.  It also asserts that ``/metrics``
exposes every catalogued metric name — exiting nonzero on a miss, so
CI catches a metric that silently fell off the exposition.

The health-plane row (``run_health``) kills a worker mid-job and
proves the full observability story on a live cluster: the critical
``lease-expiry-rate`` SLO rule fires and resolves, the event log holds
the job's complete submit→lease→expire→requeue→complete chain on ONE
trace id (and every record carries a trace id — nonzero exit
otherwise), ``GET /slo`` serves every default rule, the OTLP export
matches the native trace span-for-span.  It writes ``BENCH_events.json`` and ``BENCH_otlp_trace.json``
for the CI artifact upload.

Standalone:   PYTHONPATH=src python benchmarks/bench_load.py
CI smoke:     PYTHONPATH=src python benchmarks/bench_load.py --smoke
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
import urllib.request

from repro.obs import catalogue_names, prometheus_name
from repro.service import PipelineClient, PipelineService
from repro.service.worker import spawn_local_workers
from repro.tomo import standard_chain


def _spec(seed: int, *, n_det: int, n_angles: int):
    return standard_chain(n_det=n_det, n_angles=n_angles, n_rows=1,
                          use_pallas=False, seed=seed)


class _StatsSampler(threading.Thread):
    """Poll ``GET /stats`` on a fixed period; keep (t, queue depth,
    active leases) samples."""

    def __init__(self, client: PipelineClient, period: float = 0.2):
        super().__init__(daemon=True)
        self.client, self.period = client, period
        self.samples: list[dict] = []
        self._halt = threading.Event()

    def run(self):
        t0 = time.time()
        while not self._halt.is_set():
            try:
                st = self.client.stats()
                self.samples.append({
                    "t": round(time.time() - t0, 3),
                    "queue_depth": st["queue"]["depth"],
                    "oldest_pending_age":
                        st["queue"]["oldest_pending_age"],
                    "active_leases": st.get("active_leases", 0)})
            except Exception:
                pass                       # server mid-shutdown: stop soon
            self._halt.wait(self.period)

    def stop(self):
        self._halt.set()


def _percentile(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank on a pre-sorted list (same rule as obs.Histogram)."""
    if not sorted_vals:
        return float("nan")
    idx = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[idx]


def check_metrics_complete(url: str) -> list[str]:
    """Every catalogued metric must appear on ``/metrics``.  Returns
    the missing names (CI fails on any)."""
    with urllib.request.urlopen(f"{url}/metrics", timeout=30) as resp:
        text = resp.read().decode("utf-8")
    return [n for n in catalogue_names()
            if prometheus_name(n) not in text]


def run_load(*, n_jobs: int, rate: float, n_workers: int,
             sweep_every: int, sweep_points: int, n_det: int,
             n_angles: int, lease_ttl: float = 10.0) -> dict:
    svc = PipelineService(workers_remote=True, lease_ttl=lease_ttl,
                          sweep_interval=0.2)
    host, port = svc.serve(port=0)
    url = f"http://{host}:{port}"
    client = PipelineClient(url, timeout=60.0)
    workers = spawn_local_workers(url, n_workers, transport="inmemory",
                                  poll=0.05, heartbeat=1.0)
    sampler = _StatsSampler(client)
    try:
        # workers online before the clock starts
        deadline = time.time() + 60
        while len(client.workers()) < n_workers:
            assert time.time() < deadline, "workers never registered"
            time.sleep(0.05)
        sampler.start()

        # -- open loop: fixed arrival times, submit on schedule even
        # if the backlog grows ------------------------------------------
        job_ids: list[str] = []
        sweep_ids: list[str] = []
        late = 0
        t0 = time.time()
        for i in range(n_jobs):
            due = t0 + i / rate
            lag = due - time.time()
            if lag > 0:
                time.sleep(lag)
            else:
                late += 1
            if sweep_every and i % sweep_every == sweep_every - 1:
                reply = client.sweep(
                    _spec(i, n_det=n_det, n_angles=n_angles),
                    {"plugin": "sinogram_filter", "param": "cutoff",
                     "values": [0.5 + 0.4 * k / max(1, sweep_points - 1)
                                for k in range(sweep_points)]})
                sweep_ids.append(reply["sweep_id"])
                job_ids.extend(reply["job_ids"])
            else:
                job_ids.append(client.submit(
                    _spec(i, n_det=n_det, n_angles=n_angles),
                    priority=i % 3))
        submit_wall = time.time() - t0

        # -- drain: wait for every submission ----------------------------
        snaps = [client.wait(j, timeout=600) for j in job_ids]
        bad = [s for s in snaps if s["state"] != "done"]
        assert not bad, f"{len(bad)} jobs not done, first: {bad[0]}"
        sampler.stop()
        sampler.join(timeout=5)

        lats = sorted(s["finished_at"] - s["submitted_at"]
                      for s in snaps)
        busy = max(s["finished_at"] for s in snaps) \
            - min(s["submitted_at"] for s in snaps)
        st = client.stats()
        depths = [s["queue_depth"] for s in sampler.samples] or [0]
        return {
            "config": {"n_submissions": n_jobs, "arrival_rate": rate,
                       "n_workers": n_workers,
                       "sweep_every": sweep_every,
                       "sweep_points": sweep_points,
                       "n_det": n_det, "n_angles": n_angles},
            "n_jobs_completed": len(snaps),
            "n_sweeps": len(sweep_ids),
            "late_submissions": late,
            "submit_wall_s": round(submit_wall, 3),
            "busy_wall_s": round(busy, 3),
            "throughput_jobs_per_s": round(len(snaps) / busy, 3),
            "latency_p50_s": round(_percentile(lats, 0.5), 4),
            "latency_p99_s": round(_percentile(lats, 0.99), 4),
            "latency_max_s": round(lats[-1], 4),
            "queue_depth_max": max(depths),
            "queue_depth_final": depths[-1],
            "queue_depth_series": sampler.samples[:500],
            "leases_expired": st["leases_expired"],
            "jobs_requeued": st["jobs_requeued"],
            "server_metrics": {
                k: v for k, v in st["metrics"].items()
                if k.startswith(("job.latency", "plugin.wall"))},
            "metrics_missing": check_metrics_complete(url),
        }
    finally:
        sampler.stop()
        for p in workers:
            if p.poll() is None:
                p.kill()
        for p in workers:
            p.wait(timeout=10)
        svc.stop()


def run_stream(*, n_det: int, n_angles: int, chunk: int = 6,
               rate: float = 8.0) -> dict:
    """Streaming-acquisition smoke (docs/streaming.md): one v2
    streaming job on a scheduler-mode service, frames POSTed at a fixed
    chunk rate, and after each chunk the time until ``GET
    /jobs/{id}/preview`` covers the new watermark — the
    ingest-to-preview latency a beamline operator would see."""
    from repro.service import ServiceError, to_spec

    svc = PipelineService(n_workers=1)
    host, port = svc.serve(port=0)
    url = f"http://{host}:{port}"
    client = PipelineClient(url, timeout=60.0)
    try:
        pl = _spec(0, n_det=n_det, n_angles=n_angles)
        entry = pl.entries[0]
        loader = entry.cls(**entry.params,
                           in_datasets=list(entry.in_datasets),
                           out_datasets=list(entry.out_datasets))
        frames = loader.load()[0].materialise()
        jid = client.submit({**to_spec(pl), "version": 2,
                             "streaming": True})
        lags: list[float] = []
        t0 = time.time()
        for i, lo in enumerate(range(0, frames.shape[0], chunk)):
            due = t0 + i / rate
            if due - time.time() > 0:
                time.sleep(due - time.time())
            out = client.ingest(jid, frames[lo:lo + chunk], lo)
            fed_at, watermark = time.time(), out["watermark"]
            # poll until the preview has folded this chunk in
            while True:
                try:
                    _, cut = client.preview(jid)
                    if cut >= watermark:
                        break
                except ServiceError as e:
                    if e.status != 409:          # 409: not started yet
                        raise
                assert time.time() - fed_at < 60, "preview never caught up"
                time.sleep(0.01)
            lags.append(time.time() - fed_at)
        client.eof(jid)
        snap = client.wait(jid, timeout=120)
        assert snap["state"] == "done", snap
        lags.sort()
        return {
            "config": {"n_det": n_det, "n_angles": n_angles,
                       "chunk": chunk, "rate": rate},
            "n_chunks": len(lags),
            "stream_wall_s": round(snap["finished_at"]
                                   - snap["submitted_at"], 3),
            "ingest_to_preview_p50_s": round(_percentile(lags, 0.5), 4),
            "ingest_to_preview_p99_s": round(_percentile(lags, 0.99), 4),
            "metrics_missing": check_metrics_complete(url),
        }
    finally:
        svc.stop()


def _downsample_spec(parent: str, factor: int = 2) -> dict:
    return {"version": 1, "plugins": [
        {"plugin": "upstream_loader",
         "params": {"data": {"from_job": parent, "dataset": "recon"}},
         "out_datasets": ["vol"]},
        {"plugin": "downsample", "params": {"factor": factor},
         "in_datasets": ["vol"], "out_datasets": ["small"]},
        {"plugin": "hdf5_saver", "in_datasets": ["small"]}]}


def _quantify_spec(parent: str) -> dict:
    return {"version": 1, "plugins": [
        {"plugin": "upstream_loader",
         "params": {"data": {"from_job": parent, "dataset": "small"}},
         "out_datasets": ["vol"]},
        {"plugin": "quantify",
         "in_datasets": ["vol"], "out_datasets": ["stats"]},
        {"plugin": "hdf5_saver", "in_datasets": ["stats"]}]}


def run_workflow(*, n_det: int, n_angles: int, n_workers: int = 2) -> dict:
    """Workflow-DAG smoke (docs/workflows.md): the 3-stage
    recon -> downsample -> quantify DAG as ONE ``POST /workflows``
    against a broker with worker subprocesses, vs the same stages
    submitted sequentially (submit, wait, submit, wait...) — the
    dependency-aware queue should hide the client round-trips."""
    import numpy as np

    from repro.service import to_spec

    svc = PipelineService(workers_remote=True, lease_ttl=10.0,
                          sweep_interval=0.2)
    host, port = svc.serve(port=0)
    url = f"http://{host}:{port}"
    client = PipelineClient(url, timeout=60.0)
    workers = spawn_local_workers(url, n_workers, transport="inmemory",
                                  poll=0.05, heartbeat=1.0)
    recon = to_spec(_spec(0, n_det=n_det, n_angles=n_angles))
    try:
        deadline = time.time() + 60
        while len(client.workers()) < n_workers:
            assert time.time() < deadline, "workers never registered"
            time.sleep(0.05)
        # sequential first: it doubles as the warm-up, so the DAG row
        # measures orchestration, not first-compile cost
        t0 = time.time()
        j1 = client.submit(recon)
        assert client.wait(j1, timeout=300)["state"] == "done"
        j2 = client.submit(_downsample_spec(j1))
        assert client.wait(j2, timeout=300)["state"] == "done"
        j3 = client.submit(_quantify_spec(j2))
        assert client.wait(j3, timeout=300)["state"] == "done"
        seq_wall = time.time() - t0

        t0 = time.time()
        client.workflow({
            "recon": {"process_list": recon},
            "downsample": {"process_list": _downsample_spec("recon")},
            "quantify": {"process_list": _quantify_spec("downsample")},
        }, workflow_id="bench-wf")
        snap = client.wait_workflow("bench-wf", timeout=300)
        dag_wall = time.time() - t0
        assert snap["state"] == "done", snap
        np.testing.assert_array_equal(
            client.result("bench-wf/quantify", "stats"),
            client.result(j3, "stats"))
        return {
            "config": {"n_det": n_det, "n_angles": n_angles,
                       "n_workers": n_workers, "n_stages": 3},
            "dag_e2e_s": round(dag_wall, 3),
            "sequential_e2e_s": round(seq_wall, 3),
            "speedup": round(seq_wall / dag_wall, 3),
            "metrics_missing": check_metrics_complete(url),
        }
    finally:
        for p in workers:
            if p.poll() is None:
                p.kill()
        for p in workers:
            p.wait(timeout=10)
        svc.stop()


def run_cold_worker(*, n_det: int, n_angles: int) -> dict:
    """The retrace-tax proof (docs/worker-protocol.md): first-job e2e
    latency of a COLD sharded worker that must jit-compile the standard
    chain, vs a FRESH worker that prefetched the broker's warm pool at
    registration and only deserializes.  The prefetched worker's first
    job must be >= 3x faster and its trace must show ``executable.fetch``
    with NO ``compile`` span."""
    import tempfile

    svc = PipelineService(workers_remote=True, lease_ttl=30.0,
                          sweep_interval=0.2,
                          executables_dir=tempfile.mkdtemp(
                              prefix="bench-exe-spool-"))
    host, port = svc.serve(port=0)
    url = f"http://{host}:{port}"
    client = PipelineClient(url, timeout=120.0)
    # paganin widens the chain to 5 compiled plugins: more retrace tax
    # on the cold side, milliseconds of extra deserialize on the warm
    spec = standard_chain(n_det=n_det, n_angles=n_angles, n_rows=1,
                          use_pallas=False, paganin=True, seed=0)

    def first_job_e2e(wid: str) -> tuple[float, list[str]]:
        """Spawn ONE fresh sharded worker (its own empty local
        executable tier), run one standard-chain job on it, return the
        client-observed e2e latency and the job's span names."""
        workers = spawn_local_workers(url, 1, transport="sharded",
                                      poll=0.02, heartbeat=5.0,
                                      worker_ids=[wid])
        try:
            deadline = time.time() + 120
            while wid not in client.workers():
                assert time.time() < deadline, "worker never registered"
                time.sleep(0.05)
            jid = client.submit(spec)
            snap = client.wait(jid, timeout=300)
            assert snap["state"] == "done", snap
            spans = [s["name"] for s in client.trace(jid)["spans"]]
            return snap["finished_at"] - snap["submitted_at"], spans
        finally:
            for p in workers:
                if p.poll() is None:
                    p.kill()
            for p in workers:
                p.wait(timeout=10)

    try:
        cold_s, cold_spans = first_job_e2e("bench-cold")
        assert "compile" in cold_spans, \
            f"cold worker never compiled? spans: {cold_spans}"
        st = svc.broker.executables.stats()
        assert st["entries"] >= 1, "cold worker uploaded nothing"

        # one retry guards the ratio against a CI scheduling hiccup on
        # the warm side (each attempt is still a fully fresh worker)
        for attempt in range(2):
            warm_s, warm_spans = first_job_e2e(
                f"bench-prefetched-{attempt}")
            assert "executable.fetch" in warm_spans, \
                f"prefetched worker never fetched: {warm_spans}"
            assert "compile" not in warm_spans, \
                f"prefetched worker still compiled: {warm_spans}"
            if cold_s / warm_s >= 3.0:
                break
        speedup = cold_s / warm_s
        assert speedup >= 3.0, \
            f"warm pool too slow: cold {cold_s:.3f}s vs " \
            f"prefetched {warm_s:.3f}s ({speedup:.2f}x < 3x)"
        return {
            "config": {"n_det": n_det, "n_angles": n_angles},
            "cold_first_job_e2e_s": round(cold_s, 4),
            "prefetched_first_job_e2e_s": round(warm_s, 4),
            "speedup": round(speedup, 2),
            "spool": svc.broker.executables.stats(),
            "metrics_missing": check_metrics_complete(url),
        }
    finally:
        svc.stop()


def run_health(*, n_det: int, n_angles: int,
               events_out: str = "BENCH_events.json",
               otlp_out: str = "BENCH_otlp_trace.json") -> dict:
    """The health-plane proof (docs/observability.md): kill a sharded
    worker mid-job and verify the SLO lifecycle, the event-log
    transition chain and the OTLP export's 1:1 span mapping.  Returns
    a dict whose ``failures`` list must be empty for CI to pass."""
    import os
    import signal
    import tempfile

    from repro.obs import default_rules, iter_spans

    failures: list[str] = []
    svc = PipelineService(
        workers_remote=True, lease_ttl=1.5, sweep_interval=0.1,
        slo_interval=0.1,
        # tighten the rate window so fire->resolve happens in seconds
        slo_spec={"lease-expiry-rate": {"window_s": 4.0}})
    host, port = svc.serve(port=0)
    url = f"http://{host}:{port}"
    client = PipelineClient(url, timeout=60.0)
    ckpt = tempfile.mkdtemp(prefix="bench-health-ckpt-")
    workers = spawn_local_workers(
        url, 2, transport="sharded", checkpoint_dir=ckpt,
        poll=0.05, heartbeat=0.3,
        worker_ids=["health-w0", "health-w1"])
    pids = dict(zip(["health-w0", "health-w1"], workers))
    try:
        deadline = time.time() + 120
        while len(client.workers()) < 2:
            assert time.time() < deadline, "workers never registered"
            time.sleep(0.05)
        assert client.health(ready=True)["ready"] is True

        # -- kill the worker holding the first lease mid-job -------------
        ids = [client.submit(_spec(i, n_det=n_det, n_angles=n_angles))
               for i in range(3)]
        while True:
            running = [s for s in (client.status(j) for j in ids)
                       if s["state"] == "running" and s["worker_id"]]
            if running:
                victim_job, victim = (running[0]["job_id"],
                                      running[0]["worker_id"])
                break
            assert time.time() < deadline, "nothing ever ran"
            time.sleep(0.02)
        os.kill(pids[victim].pid, signal.SIGKILL)

        # the critical rule must fire: readiness flips to 503
        while client.health(ready=True)["ready"]:
            assert time.time() < deadline, "expiry rule never fired"
            time.sleep(0.05)
        if "lease-expiry-rate" not in client.slo()["critical_firing"]:
            failures.append("slo_rule_never_fired")

        # the survivor drains everything (the killed job resumes from
        # its shared checkpoint or restarts)
        snaps = [client.wait(j, timeout=300) for j in ids]
        bad = [s for s in snaps if s["state"] != "done"]
        assert not bad, f"{len(bad)} jobs not done, first: {bad[0]}"
        # ...and once the rate window slides past the expiry the rule
        # resolves: readiness back to 200
        while not client.health(ready=True)["ready"]:
            assert time.time() < deadline, "expiry rule never resolved"
            time.sleep(0.1)

        # -- GET /slo: every default rule present, fire+resolve counted --
        slo = client.slo()
        by_rule = {r["name"]: r for r in slo["rules"]}
        missing_rules = [r.name for r in default_rules()
                         if r.name not in by_rule]
        if missing_rules:
            failures.append(f"slo_missing_rules:{missing_rules}")
        expiry = by_rule.get("lease-expiry-rate", {})
        if not (expiry.get("fired", 0) >= 1
                and expiry.get("resolved", 0) >= 1
                and expiry.get("state") == "ok"):
            failures.append(f"slo_lifecycle_incomplete:{expiry}")

        # -- event log: full transition chain on ONE trace id ------------
        events = client.events()["events"]
        with open(events_out, "w") as fh:
            json.dump(events, fh, indent=2)
        if any(not e["trace_id"] for e in events):
            failures.append("event_records_missing_trace_id")
        mine = [e for e in events if e["job_id"] == victim_job]
        chain = [e["event"] for e in mine]
        for needed in ("job.submit", "job.lease", "lease.expire",
                       "job.requeue", "job.complete"):
            if needed not in chain:
                failures.append(f"event_chain_missing:{needed}")
        if len({e["trace_id"] for e in mine}) != 1:
            failures.append("event_chain_trace_id_not_unique")
        for name in ("alert.firing", "alert.resolved"):
            n = sum(1 for e in events if e["event"] == name
                    and e["attrs"].get("rule") == "lease-expiry-rate")
            if n != 1:
                failures.append(f"alert_event_count:{name}={n}")

        # -- OTLP export: spans match the native trace 1:1 ---------------
        native = client.trace(victim_job)["spans"]
        otlp = client.trace(victim_job, otlp=True)
        with open(otlp_out, "w") as fh:
            json.dump(otlp, fh, indent=2)
        exported = list(iter_spans(otlp))
        if len(exported) != len(native):
            failures.append(f"otlp_span_count:{len(exported)}"
                            f"!={len(native)}")
        native_ids = {str(s["span_id"]).lower().rjust(16, "0")
                      for s in native}
        otlp_ids = {s["spanId"] for s in exported}
        if native_ids != otlp_ids:
            failures.append("otlp_span_ids_mismatch")

        resumed = next((s for s in snaps
                        if s["job_id"] == victim_job), {})
        st = client.stats()
        return {
            "config": {"n_det": n_det, "n_angles": n_angles},
            "leases_expired": st["leases_expired"],
            "jobs_requeued": st["jobs_requeued"],
            "victim_job_attempts": resumed.get("attempt"),
            "victim_resumed_from": resumed.get("resumed_from"),
            "slo_rules": sorted(by_rule),
            "expiry_rule": {k: expiry.get(k)
                            for k in ("fired", "resolved", "state")},
            "n_events": len(events),
            "n_spans_native": len(native),
            "n_spans_otlp": len(exported),
            "events_out": events_out, "otlp_out": otlp_out,
            "failures": failures,
            "metrics_missing": check_metrics_complete(url),
        }
    finally:
        for p in workers:
            if p.poll() is None:
                p.kill()
        for p in workers:
            p.wait(timeout=10)
        svc.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small CI config (seconds, 2 workers)")
    ap.add_argument("--jobs", type=int, default=None,
                    help="number of submissions (solo jobs + sweeps)")
    ap.add_argument("--rate", type=float, default=None,
                    help="arrival rate, submissions/s")
    ap.add_argument("--workers", type=int, default=None,
                    help="worker subprocesses")
    ap.add_argument("--sweep-every", type=int, default=4,
                    help="every Kth submission is a sweep (0: none)")
    ap.add_argument("--sweep-points", type=int, default=3,
                    help="variants per sweep")
    ap.add_argument("--out", default="BENCH_service.json")
    args = ap.parse_args(argv)

    if args.smoke:
        cfg = dict(n_jobs=args.jobs or 8, rate=args.rate or 4.0,
                   n_workers=args.workers or 2, n_det=16, n_angles=8)
    else:
        cfg = dict(n_jobs=args.jobs or 40, rate=args.rate or 2.0,
                   n_workers=args.workers or 4, n_det=48, n_angles=48)
    result = run_load(sweep_every=args.sweep_every,
                      sweep_points=args.sweep_points, **cfg)
    result["streaming"] = run_stream(n_det=cfg["n_det"],
                                     n_angles=cfg["n_angles"])
    result["workflow"] = run_workflow(n_det=cfg["n_det"],
                                      n_angles=cfg["n_angles"],
                                      n_workers=cfg["n_workers"])
    result["cold_worker"] = run_cold_worker(n_det=cfg["n_det"],
                                            n_angles=cfg["n_angles"])
    result["health"] = run_health(n_det=cfg["n_det"],
                                  n_angles=cfg["n_angles"])

    with open(args.out, "w") as fh:
        json.dump(result, fh, indent=2)
    print(f"{result['n_jobs_completed']} jobs "
          f"({result['n_sweeps']} sweeps) @ "
          f"{result['throughput_jobs_per_s']} jobs/s — "
          f"p50 {result['latency_p50_s']}s, "
          f"p99 {result['latency_p99_s']}s, "
          f"queue depth max {result['queue_depth_max']}, "
          f"{result['leases_expired']} lease expiries "
          f"-> {args.out}")
    sm = result["streaming"]
    print(f"streaming: {sm['n_chunks']} chunks, ingest-to-preview "
          f"p50 {sm['ingest_to_preview_p50_s']}s, "
          f"p99 {sm['ingest_to_preview_p99_s']}s")
    wf = result["workflow"]
    print(f"workflow: 3-stage DAG e2e {wf['dag_e2e_s']}s vs "
          f"sequential {wf['sequential_e2e_s']}s "
          f"({wf['speedup']}x)")
    cw = result["cold_worker"]
    print(f"cold worker: first job {cw['cold_first_job_e2e_s']}s "
          f"compiling vs {cw['prefetched_first_job_e2e_s']}s "
          f"prefetched ({cw['speedup']}x — the retrace tax)")
    hp = result["health"]
    print(f"health plane: expiry rule fired/resolved "
          f"{hp['expiry_rule']['fired']}/{hp['expiry_rule']['resolved']}"
          f", {hp['n_events']} events, {hp['n_spans_otlp']} OTLP spans "
          f"(= {hp['n_spans_native']} native) "
          f"-> {hp['events_out']}, {hp['otlp_out']}")
    missing = sorted(set(result["metrics_missing"])
                     | set(sm["metrics_missing"])
                     | set(wf["metrics_missing"])
                     | set(cw["metrics_missing"])
                     | set(hp["metrics_missing"]))
    failed = False
    if missing:
        print(f"MISSING from /metrics: {missing}", file=sys.stderr)
        failed = True
    if hp["failures"]:
        print(f"HEALTH-PLANE failures: {hp['failures']}",
              file=sys.stderr)
        failed = True
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
