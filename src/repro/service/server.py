"""HTTP front end over the JobQueue — cross-process serving.

The scheduler (ROADMAP PR 1) and checkpoint/resume layer (PR 2) were
only reachable in-process; this module is the step that turns them into
facility infrastructure in the Nanosurveyor/Daisy sense: a remote
submit/monitor interface over the scheduler, so the paper's "3000
scientific users per year" can submit process lists to a pipeline they
do not run themselves.  Stdlib only (``http.server``) — no new deps.

Endpoints (JSON unless noted; see ``docs/service.md``):

==========================  ==========================================
``POST /jobs``              submit a spec envelope -> ``{"job_id"}``;
                            400 on validation errors, 409 on duplicate
                            active id, **429** on admission rejection
``GET /jobs``               every job's ``Job.snapshot()``
``GET /jobs/{id}``          one snapshot (``running(plugin i/N)``
                            progress, ``resumed_from``, ...)
``GET /jobs/{id}/result``   output dataset as ``.npy`` bytes
                            (``?dataset=`` selects; chunk-streamed)
``DELETE /jobs/{id}``       cancel a queued job (409 once dispatched)
``POST /sweeps``            expand a parameter-sweep envelope into a
                            gang of variant jobs (``docs/sweeps.md``)
``GET /sweeps[/{id}]``      sweep group status (per-variant snapshots,
                            ``best_variant`` when a metric was set)
``GET /sweeps/{id}/result`` the stacked ``.npy`` — parameter axes as
                            the new leading dimension(s)
``DELETE /sweeps/{id}``     cancel every live variant
``POST /workflows``         submit a spec-v3 DAG of process lists in
                            one atomic request (``docs/workflows.md``;
                            400 on cycles/dangling refs)
``GET /workflows[/{id}]``   workflow group status (per-node snapshots,
                            DAG edges, aggregate state)
``GET /workflows/{id}/trace``  linked trace: every node's span
                            timeline in one document
``DELETE /workflows/{id}``  cancel every live node (queued downstream
                            nodes cascade automatically)
``GET /jobs/{id}/trace``    the job's cross-process span timeline
                            (``?format=text`` renders an ASCII gantt,
                            ``?format=otlp`` an OTLP/JSON export doc;
                            ``docs/observability.md``)
``POST /jobs/{id}/frames``  streaming ingest: one raw ``.npy`` chunk +
                            ``X-Start-Frame`` header (409 on
                            out-of-order/duplicate; docs/streaming.md)
``POST /jobs/{id}/eof``     end of acquisition for a streaming job
``GET /jobs/{id}/frames``   buffered frames from ``?start=`` on — how
                            broker-mode workers pull the stream
``GET /jobs/{id}/preview``  partial reconstruction over the frames
                            ingested so far (before EOF)
``GET /executables``        the broker spool's hottest executable
                            signatures (warm-pool prefetch list;
                            token-authed, broker mode)
``GET /executables/{sig}``  one serialized executable as octet-stream
                            bytes (token-authed, broker mode)
``PUT /executables/{sig}``  worker upload of a serialized executable
                            (``X-Worker-Id``/``X-Worker-Secret``)
``GET /metrics``            Prometheus text exposition of the metrics
                            registry (also JSON under ``/stats``)
``GET /stats``              scheduler + compile-cache + metrics counters
``GET /plugins``            the wire-format plugin registry
``GET /events``             structured event log tail (``?since=``
                            cursor + ``?limit=``; docs/observability.md)
``GET /slo``                SLO rule states + alert lifecycle snapshot
``GET /cluster``            per-worker scoreboard (broker mode: leases,
                            heartbeat staleness, last error, prefetch)
``GET /healthz``            liveness probe; ``?ready=1`` consults the
                            SLO engine (503 while a critical rule fires)
==========================  ==========================================

Results are streamed straight out of the transport's chunk-addressed
files (``ChunkedFile`` — the checkpoint layer's on-disk layout) one
chunk-row slab at a time, so serving a large reconstruction never holds
the dense volume in server RAM; only in-memory/sharded backings are
materialised before the write.
"""
from __future__ import annotations

import hmac
import io
import json
import os
import re
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable
from urllib.parse import parse_qs, unquote, urlparse

import numpy as np

import jax

from ..core.process_list import ProcessListError
from ..core.transport import ChunkedFile, Transport
from ..obs.export import trace_to_otlp
from ..obs.log import EventLog
from ..obs.metrics import MetricsRegistry, register_catalogue
from ..obs.slo import SloEngine
from ..obs.trace import Span, TraceSpool, render_gantt, use_trace
from .checkpoint import CheckpointStore
from .compile_cache import CompileCache
from .job import Job, JobState
from .queue import JobQueue, QueueFull
from .scheduler import (LeaseLost, PipelineScheduler, WorkerAuthError,
                        WorkerBroker, observe_spans)
from .sweep import SweepError, SweepGroup, SweepManager
from .wire import WireError, from_spec, registry_spec
from .workflow import WorkflowError, WorkflowGroup, WorkflowManager

_JOB_RE = re.compile(r"^/jobs/([^/]+)$")
_RESULT_RE = re.compile(r"^/jobs/([^/]+)/result$")
_FRAMES_RE = re.compile(r"^/jobs/([^/]+)/frames$")
_EOF_RE = re.compile(r"^/jobs/([^/]+)/eof$")
_PREVIEW_RE = re.compile(r"^/jobs/([^/]+)/preview$")
_TRACE_RE = re.compile(r"^/jobs/([^/]+)/trace$")
_PROGRESS_RE = re.compile(r"^/jobs/([^/]+)/progress$")
_COMPLETE_RE = re.compile(r"^/jobs/([^/]+)/complete$")
_SWEEP_RE = re.compile(r"^/sweeps/([^/]+)$")
_SWEEP_RESULT_RE = re.compile(r"^/sweeps/([^/]+)/result$")
_WORKFLOW_RE = re.compile(r"^/workflows/([^/]+)$")
_WORKFLOW_TRACE_RE = re.compile(r"^/workflows/([^/]+)/trace$")
#: executable signatures are sha256 hex (compile_cache.executable_signature)
_EXEC_RE = re.compile(r"^/executables/([0-9a-f]{8,128})$")


class PipelineService:
    """A JobQueue + PipelineScheduler pair wrapped for HTTP serving.

    Owns the queue, the scheduler, the shared :class:`CompileCache`, and
    (optionally) a :class:`CheckpointStore`, and knows how to admit a
    wire-format spec envelope and stream results back out.  Use
    :meth:`serve` to bind the HTTP front end, or drive
    :meth:`submit_envelope`/:meth:`cancel` in-process.
    """

    def __init__(self, *,
                 transport_factory: Callable[[Job], Transport] | None = None,
                 n_workers: int = 2,
                 max_pending: int | None = 64,
                 max_history: int | None = 256,
                 checkpoints: CheckpointStore | None = None,
                 batch_identical: bool = False,
                 batch_max: int = 4,
                 fuse: bool = False,
                 compile_cache: CompileCache | None = None,
                 workers_remote: bool = False,
                 lease_ttl: float = 15.0,
                 sweep_interval: float | None = None,
                 results_dir: str | None = None,
                 max_sweep_variants: int = 64,
                 token: str | None = None,
                 trace_spool: TraceSpool | str | None = None,
                 executables_dir: str | None = None,
                 events_max: int = 2048,
                 slo_spec: dict[str, Any] | None = None,
                 slo_interval: float = 1.0):
        """Args mirror :class:`PipelineScheduler`; ``max_pending``
        bounds admission (HTTP 429 past it) and ``max_history`` bounds
        retained terminal jobs (a pruned job's result is gone — 404).

        ``token`` (satellite: auth hardening) arms shared-secret bearer
        auth: every MUTATING verb (POST/PUT/DELETE — including the
        worker protocol and frame ingest) is rejected 401 unless it
        carries ``Authorization: Bearer <token>``; reads stay open.
        ``trace_spool`` (a :class:`TraceSpool` or a directory path)
        retains terminal-job traces past ``max_history`` eviction —
        ``GET /jobs/{id}/trace`` falls back to it.
        ``executables_dir`` roots the persistent executable tier: in
        broker mode it is the broker's upload/prefetch spool
        (``GET/PUT /executables/{sig}``, default
        :func:`~repro.service.compile_cache.default_executables_dir`); in
        scheduler mode it becomes the service CompileCache's disk store
        so compiled programs survive restarts.

        ``workers_remote=True`` is **broker mode**: instead of
        in-process scheduler threads, detached :class:`PipelineWorker`
        processes register over HTTP and pull jobs via leases
        (``lease_ttl``/``sweep_interval``/``results_dir`` configure the
        :class:`WorkerBroker`; ``transport_factory``/``n_workers``/
        gang options are worker-side concerns and are ignored here).

        The health plane (docs/observability.md): ``events_max`` bounds
        the structured event-log ring (``GET /events``), ``slo_spec``
        overrides/extends the default SLO rules
        (:func:`repro.obs.slo.rules_from_spec`), and ``slo_interval``
        paces the background evaluator that walks alerts through
        pending → firing → resolved.
        """
        # explicit None-check: an EMPTY CompileCache is falsy (__len__)
        if compile_cache is None:
            # scheduler mode gets the persistent tier on the service's
            # own cache; broker mode roots its upload spool there
            # instead (workers own their caches)
            compile_cache = CompileCache(
                store=None if workers_remote else executables_dir)
        self.compile_cache = compile_cache
        self.queue = JobQueue(max_pending=max_pending,
                              max_history=max_history)
        # one registry per service (docs/observability.md); the full
        # catalogue is pre-registered so /metrics is complete from the
        # first scrape
        self.metrics = MetricsRegistry()
        register_catalogue(self.metrics)
        # the structured event log: every queue/scheduler/broker state
        # transition lands here as one bounded JSON record
        self.events = EventLog(max_events=events_max)
        self.queue.events = self.events
        self.slo = SloEngine(self.metrics, self.events, spec=slo_spec)
        self.slo_interval = max(0.05, float(slo_interval))
        self.scheduler: PipelineScheduler | None = None
        self.broker: WorkerBroker | None = None
        if workers_remote:
            self.broker = WorkerBroker(
                self.queue, lease_ttl=lease_ttl,
                sweep_interval=sweep_interval, results_dir=results_dir,
                metrics=self.metrics, events=self.events,
                executables_dir=executables_dir)
        else:
            self.scheduler = PipelineScheduler(
                self.queue, transport_factory=transport_factory,
                n_workers=n_workers, checkpoints=checkpoints,
                batch_identical=batch_identical, batch_max=batch_max,
                fuse=fuse, compile_cache=self.compile_cache,
                metrics=self.metrics, events=self.events)
        self.sweeps = SweepManager(self.queue, fetch=self._variant_array,
                                   max_variants=max_sweep_variants)
        self.workflows = WorkflowManager(self.queue)
        self.token = token
        self.trace_spool = (TraceSpool(trace_spool)
                            if isinstance(trace_spool, str) else trace_spool)
        if self.trace_spool is not None:
            spool = self.trace_spool
            self.queue.add_evict_hook(
                lambda job: spool.put(job.job_id, job.trace))
        # eviction backstop: a terminal streaming job's retained frame
        # chunks must not outlive the job record
        self.queue.add_evict_hook(
            lambda job: job.stream.drop_buffers() if job.stream else None)
        self._wire_gauges()
        self._httpd: ThreadingHTTPServer | None = None
        self._http_thread: threading.Thread | None = None
        self._slo_thread: threading.Thread | None = None
        self._slo_stop = threading.Event()

    def _wire_gauges(self) -> None:
        """Bind the callback gauges: these read live state at scrape
        time rather than being pushed on every event."""
        m = self.metrics
        m.gauge("queue.depth").set_function(self.queue.pending)
        m.gauge("queue.oldest_age_s").set_function(
            lambda: self.queue.queue_info()["oldest_pending_age"] or 0.0)
        m.gauge("compile.cache.hits").set_function(
            lambda: self.compile_cache.hits)
        m.gauge("compile.cache.misses").set_function(
            lambda: self.compile_cache.misses)
        m.gauge("compile.cache.disk.hits").set_function(
            lambda: self.compile_cache.disk_hits)
        m.gauge("compile.cache.disk.misses").set_function(
            lambda: self.compile_cache.disk_misses)
        broker = self.broker
        m.gauge("executables.spool.bytes").set_function(
            broker.executables.total_bytes if broker is not None
            else lambda: (self.compile_cache.store.total_bytes()
                          if self.compile_cache.store is not None else 0))
        m.gauge("leases.active").set_function(
            broker.n_active_leases if broker is not None else lambda: 0)
        m.gauge("workers.registered").set_function(
            broker.n_workers if broker is not None else lambda: 0)
        m.gauge("slo.firing").set_function(
            lambda: float(self.slo.n_firing()))
        m.gauge("events.head").set_function(
            lambda: float(self.events.head))

    # -- service operations (HTTP-independent) -------------------------
    def submit_envelope(self, envelope: dict[str, Any]) -> Job:
        """Admit one submission envelope::

            {"process_list": <spec v1>,   # required
             "priority": 0, "job_id": null, "metadata": {},
             "trace_id": null}            # correlate with external traces

        Deserialises the spec (:func:`~repro.service.wire.from_spec`),
        runs the pre-flight ``ProcessList.check()`` so structurally
        broken chains are rejected before admission, then enqueues.

        Returns: the queued :class:`Job`.
        Raises:
            WireError / ProcessListError: invalid spec (HTTP 400).
            ValueError: duplicate active job id (HTTP 409).
            QueueFull: admission control rejected (HTTP 429).
        """
        if not isinstance(envelope, dict) or \
                "process_list" not in envelope:
            raise WireError('body must be an object with a '
                            '"process_list" spec')
        priority = envelope.get("priority", 0)
        if not isinstance(priority, int) or isinstance(priority, bool):
            raise WireError(f"priority must be an integer, got "
                            f"{priority!r}")
        job_id = envelope.get("job_id")
        if job_id is not None and not isinstance(job_id, str):
            raise WireError(f"job_id must be a string, got {job_id!r}")
        metadata = envelope.get("metadata") or {}
        if not isinstance(metadata, dict):
            raise WireError("metadata must be an object")
        trace_id = envelope.get("trace_id")
        if trace_id is not None and not isinstance(trace_id, str):
            raise WireError(f"trace_id must be a string, got "
                            f"{trace_id!r}")
        pl = from_spec(envelope["process_list"])
        pl.check()
        job = self.queue.submit(pl, priority=priority, job_id=job_id,
                                metadata=metadata, trace_id=trace_id)
        self.metrics.counter("jobs.submitted").inc()
        return job

    def cancel(self, job_id: str) -> dict[str, Any]:
        """Cancel ``job_id`` if still queued — or, in broker mode, flag
        a LEASED job so its worker's next heartbeat gets a ``cancelled``
        verdict.  Returns ``{"job_id", "cancelled", "state"}`` (plus
        ``"pending": True`` for the leased case, where the terminal
        state lands at the next heartbeat); ``cancelled`` is False for a
        job already terminal.  Raises KeyError if unknown."""
        cancelled = self.queue.cancel(job_id)
        job = self.queue.job(job_id)
        out = {"job_id": job_id, "cancelled": cancelled,
               "state": job.state.value}
        # a queue-side cancel (and any dependency cascade it triggers)
        # is observed by the queue's terminal hooks — registered by both
        # scheduler and broker — so outcome metrics stay exactly-once
        if not cancelled and self.broker is not None \
                and self.broker.request_cancel(job_id):
            out.update(cancelled=True, pending=True)
        return out

    # -- streaming ingest (docs/streaming.md) ---------------------------
    def _streaming_job(self, job_id: str) -> Job:
        """The job, checked to be a live streaming one.  Raises KeyError
        (404) if unknown, RuntimeError (409) otherwise."""
        job = self.queue.job(job_id)
        if not job.streaming:
            raise RuntimeError(f"job {job_id!r} is not a streaming job "
                               f'(submit with spec v2 "streaming": true)')
        return job

    def ingest_frames(self, job_id: str, frames: np.ndarray,
                      start: int) -> dict[str, Any]:
        """Accept one contiguous frame chunk (``POST /jobs/{id}/frames``).

        ``start`` must equal the current ingest watermark — out-of-order
        and duplicate chunks are rejected (RuntimeError → HTTP 409) so
        the on-disk prefix is always exact.  Wakes the queue (a parked
        streaming job becomes leasable again) and any in-process driver
        waiting on the stream condition."""
        job = self._streaming_job(job_id)
        if job.state.terminal():
            raise RuntimeError(f"job {job_id!r} is {job.state.value}; "
                               f"ingest is closed")
        frames = np.ascontiguousarray(frames)
        if frames.ndim < 1 or frames.shape[0] == 0:
            raise RuntimeError("frames chunk must have >= 1 frame on "
                               "axis 0")
        st = job.stream
        with st.lock:
            if st.eof:
                raise RuntimeError(f"job {job_id!r} already got EOF; no "
                                   f"more frames accepted")
            if start != st.watermark:
                raise RuntimeError(
                    f"out-of-order ingest for job {job_id!r}: chunk "
                    f"starts at frame {start} but the watermark is "
                    f"{st.watermark} (duplicate or gap)")
            watermark = st.append(frames, start)
            st.cond.notify_all()
        self.queue.kick()
        self.metrics.counter("stream.frames.ingested").inc(
            int(frames.shape[0]))
        return {"job_id": job_id, "start": int(start),
                "count": int(frames.shape[0]), "watermark": watermark}

    def mark_eof(self, job_id: str) -> dict[str, Any]:
        """End of acquisition (``POST /jobs/{id}/eof``): no more frames
        will arrive.  A second EOF on a live stream is a protocol error
        (409), like a duplicate chunk — but EOF on a stream that already
        ran to completion succeeds: the loader declares its total frame
        count, so a fast executor can finish the moment the last frame
        lands, racing ahead of the producer's EOF."""
        job = self._streaming_job(job_id)
        st = job.stream
        if job.state is JobState.DONE:
            with st.lock:
                st.eof = True
                return {"job_id": job_id, "eof": True,
                        "watermark": st.watermark}
        if job.state.terminal():
            raise RuntimeError(f"job {job_id!r} is {job.state.value}; "
                               f"ingest is closed")
        with st.lock:
            if st.eof:
                raise RuntimeError(f"job {job_id!r} already got EOF")
            st.eof = True
            watermark = st.watermark
            st.cond.notify_all()
        self.queue.kick()
        return {"job_id": job_id, "eof": True, "watermark": watermark}

    def preview(self, job_id: str) -> tuple[np.ndarray, int]:
        """Partial reconstruction over the frames ingested so far
        (``GET /jobs/{id}/preview``) — ``(array, frames_covered)``.

        Scheduler mode computes it on demand from the live runner
        (serialised against the pump loop by ``stream.exec_lock``);
        broker mode serves the newest preview the worker uploaded.
        Raises RuntimeError/ValueError (→ 409) while no preview can be
        produced yet."""
        job = self._streaming_job(job_id)
        if self.broker is not None:
            path = job.remote_results.get("__preview__")
            if path is None or not os.path.exists(path):
                raise RuntimeError(
                    "no preview available yet (the worker has not "
                    "uploaded one)")
            return np.load(path), job.preview_watermark
        runner = job.runner
        if runner is None or not runner.streaming:
            raise RuntimeError(
                "no preview available yet (the job has not started)")
        with job.stream.exec_lock:
            arr, cut = runner.preview()
        job.preview_watermark = max(job.preview_watermark, cut)
        return arr, cut

    # -- parameter sweeps (docs/sweeps.md) ------------------------------
    def submit_sweep(self, envelope: dict[str, Any]) -> SweepGroup:
        """Admit one sweep envelope (``POST /sweeps``): the spec plus a
        ``sweep`` grid block, expanded into variant jobs submitted
        atomically so the gang path batches them.  See
        :meth:`SweepManager.submit` for the error contract."""
        group = self.sweeps.submit(envelope)
        self.metrics.counter("jobs.submitted").inc(group.n_variants)
        return group

    def cancel_sweep(self, sweep_id: str) -> dict[str, Any]:
        """Cancel every live variant of ``sweep_id``
        (``DELETE /sweeps/{id}``) — queued variants cancel immediately,
        leased ones at their worker's next heartbeat.  Raises KeyError
        if unknown."""
        return self.sweeps.cancel(sweep_id, self.cancel)

    # -- workflow DAGs (docs/workflows.md) ------------------------------
    def submit_workflow(self, envelope: dict[str, Any]) -> WorkflowGroup:
        """Admit one spec-v3 workflow envelope (``POST /workflows``): a
        DAG of process lists validated (cycles, dangling refs → 400)
        and admitted atomically.  See :meth:`WorkflowManager.submit`
        for the error contract."""
        group = self.workflows.submit(envelope)
        self.metrics.counter("jobs.submitted").inc(group.n_nodes)
        return group

    def cancel_workflow(self, workflow_id: str) -> dict[str, Any]:
        """Cancel every live node of ``workflow_id``
        (``DELETE /workflows/{id}``) — queued nodes cancel immediately
        (their downstream cones cascade), leased ones at their worker's
        next heartbeat.  Raises KeyError if unknown."""
        return self.workflows.cancel(workflow_id, self.cancel)

    def workflow_trace(self, workflow_id: str) -> dict[str, Any]:
        """The workflow-level linked trace (``GET
        /workflows/{id}/trace``): per-node span timelines, falling back
        to the trace spool for evicted node jobs."""
        return self.workflows.trace(workflow_id, self._job_trace_doc)

    def _job_trace_doc(self, job_id: str) -> dict[str, Any]:
        """One job's trace as a wire document — live trace when the job
        record survives, trace-spool fallback after eviction.  Raises
        KeyError when neither has it."""
        try:
            job = self.queue.job(job_id)
        except KeyError:
            rec = (self.trace_spool.get(job_id)
                   if self.trace_spool is not None else None)
            if rec is None:
                raise
            return rec
        return {"job_id": job_id, **job.trace.to_wire()}

    def _variant_array(self, job_id: str, dataset: str | None = None
                       ) -> np.ndarray:
        """One DONE variant's result as a host array — covers both the
        in-process runner path and the broker-mode ``.npy`` spool (the
        SweepManager's ``fetch`` hook, O(variant) RAM)."""
        remote = self.result_file(job_id, dataset)
        if remote is not None:
            return np.load(remote[1])
        ds, transport = self.result_dataset(job_id, dataset)
        return np.ascontiguousarray(np.asarray(transport.read(ds)))

    # -- health plane (docs/observability.md) ---------------------------
    def readiness(self) -> tuple[int, dict[str, Any]]:
        """The degrade-aware readiness verdict
        (``GET /healthz?ready=1``): evaluate the SLO engine NOW, answer
        ``(503, detail)`` while any critical rule is firing, else
        ``(200, ok)``.  Liveness (plain ``/healthz``) never consults
        the engine — a sick-but-alive service must not be restarted by
        its liveness probe."""
        self.slo.evaluate()
        critical = self.slo.critical_firing()
        if critical:
            return 503, {"ok": False, "ready": False,
                         "error": "critical SLO rule firing",
                         "firing": [r["name"] for r in critical],
                         "detail": critical,
                         "pending": self.queue.pending()}
        return 200, {"ok": True, "ready": True,
                     "pending": self.queue.pending()}

    def slo_snapshot(self) -> dict[str, Any]:
        """Fresh ``GET /slo`` payload (evaluates first, so a scrape
        never reports stale lifecycle states)."""
        self.slo.evaluate()
        return self.slo.snapshot()

    def _slo_loop(self, stop: threading.Event) -> None:
        while not stop.wait(self.slo_interval):
            self.slo.evaluate()

    def stats(self) -> dict[str, Any]:
        """Scheduler (or broker) counters + compile-cache hit rates +
        sweep-group counters + the metrics-registry snapshot
        (``GET /stats``)."""
        out = (self.broker.stats() if self.broker is not None
               else self.scheduler.stats())
        out["sweeps"] = self.sweeps.stats()
        out["workflows"] = self.workflows.stats()
        out["metrics"] = self.metrics.snapshot()
        return out

    def result_dataset(self, job_id: str, dataset: str | None = None):
        """Resolve a finished job's output dataset + its transport.

        Args:
            job_id: a DONE job still within ``max_history``.
            dataset: dataset name; default = the chain's first saver
                output (:meth:`PluginRunner.result_names`).

        Returns: ``(DataSet, Transport)``.
        Raises:
            KeyError: unknown job or unknown dataset name.
            RuntimeError: job not DONE yet, or its runner was pruned.
        """
        job = self.queue.job(job_id)
        if job.state is not JobState.DONE:
            raise RuntimeError(f"job {job_id!r} is {job.status!r}, "
                               f"not done")
        runner = job.runner
        if runner is None and job.remote_results:
            raise RuntimeError(          # broker-mode: served from files
                f"job {job_id!r} ran on a remote worker; its results "
                f"are .npy files, not live datasets")
        if runner is None:
            raise RuntimeError(f"job {job_id!r} result was evicted "
                               f"(max_history)")
        name = dataset or (runner.result_names() or [None])[0]
        if name is None or name not in runner.datasets:
            raise KeyError(
                f"job {job_id!r} has no dataset {name!r} "
                f"(available: {sorted(runner.datasets)})")
        return runner.datasets[name], runner.transport

    def result_file(self, job_id: str, dataset: str | None = None
                    ) -> tuple[str, str] | None:
        """Broker-mode result lookup: ``(name, path)`` of the ``.npy`` a
        remote worker handed over for ``dataset`` (default: the first
        reported), or None when this job has no remote results
        (in-process path).

        Raises:
            KeyError: unknown job, or remote results exist but not for
                ``dataset``.
            RuntimeError: job not DONE yet.
        """
        job = self.queue.job(job_id)
        if not job.remote_results:
            return None
        if job.state is not JobState.DONE:
            raise RuntimeError(f"job {job_id!r} is {job.status!r}, "
                               f"not done")
        # dunder names (the streaming "__preview__" upload) are service
        # plumbing, never a default result
        name = dataset or next(
            (k for k in job.remote_results if not k.startswith("__")),
            next(iter(job.remote_results)))
        path = job.remote_results.get(name)
        if path is None or not os.path.exists(path):
            raise KeyError(
                f"job {job_id!r} has no result dataset {name!r} "
                f"(available: {sorted(job.remote_results)})")
        return name, path

    # -- lifecycle ------------------------------------------------------
    def serve(self, host: str = "127.0.0.1", port: int = 8080,
              block: bool = False) -> tuple[str, int]:
        """Start the scheduler workers and the HTTP front end.

        Args:
            host/port: bind address (``port=0`` picks an ephemeral port).
            block: run ``serve_forever`` on the calling thread (CLI
                mode) instead of a daemon thread.

        Returns: the bound ``(host, port)``.
        """
        if self.broker is not None:
            self.broker.start()
        else:
            self.scheduler.start()
        if self._slo_thread is None:
            self._slo_stop = threading.Event()
            self._slo_thread = threading.Thread(
                target=self._slo_loop, args=(self._slo_stop,),
                name="slo-eval", daemon=True)
            self._slo_thread.start()
        service = self

        class Handler(_PipelineHandler):
            pass

        Handler.service = service
        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._httpd.daemon_threads = True
        addr = self._httpd.server_address[:2]
        if block:
            try:
                self._httpd.serve_forever()
            finally:
                self.stop()
        else:
            self._http_thread = threading.Thread(
                target=self._httpd.serve_forever, name="pipeline-http",
                daemon=True)
            self._http_thread.start()
        return addr

    def stop(self) -> None:
        """Shut down the HTTP server (if serving) and the scheduler
        workers / broker sweep thread."""
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self._http_thread is not None:
            self._http_thread.join(timeout=10)
            self._http_thread = None
        if self._slo_thread is not None:
            self._slo_stop.set()
            self._slo_thread.join(timeout=10)
            self._slo_thread = None
        if self.broker is not None:
            self.broker.shutdown()
        if self.scheduler is not None:
            self.scheduler.shutdown()


# ----------------------------------------------------------------------
def _npy_header(shape: tuple[int, ...], dtype) -> bytes:
    """The ``.npy`` v1 magic + header for a C-ordered array, so a result
    body can be streamed without building the array in RAM."""
    from numpy.lib import format as npy
    buf = io.BytesIO()
    npy.write_array_header_1_0(
        buf, {"descr": npy.dtype_to_descr(np.dtype(dtype)),
              "fortran_order": False, "shape": tuple(shape)})
    return buf.getvalue()     # write_array_header_1_0 includes the magic


class _PipelineHandler(BaseHTTPRequestHandler):
    """Routes HTTP verbs to the bound :class:`PipelineService`."""

    service: PipelineService = None   # bound per-server in serve()
    server_version = "SavuPipeline/1"
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):   # quiet by default (tests)
        pass

    # -- helpers --------------------------------------------------------
    def _json(self, code: int, obj: Any) -> None:
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _error(self, code: int, message: str, **extra) -> None:
        self._json(code, {"error": message, **extra})

    def _text(self, code: int, text: str,
              content_type: str = "text/plain; charset=utf-8") -> None:
        body = text.encode()
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _read_body(self) -> Any:
        length = int(self.headers.get("Content-Length") or 0)
        raw = self.rfile.read(length) if length else b""
        if not raw:
            raise WireError("empty request body")
        try:
            return json.loads(raw)
        except json.JSONDecodeError as e:
            raise WireError(f"request body is not valid JSON: {e}")

    def _drain_body(self) -> None:
        """Consume an unread request body before replying — a keep-alive
        connection would otherwise parse the leftover bytes as the next
        request line."""
        length = int(self.headers.get("Content-Length") or 0)
        if length:
            self.rfile.read(length)

    def _authorised(self) -> bool:
        """Shared-secret bearer check for mutating verbs.  No token
        configured = open service (the pre-auth behaviour)."""
        token = self.service.token
        if token is None:
            return True
        got = self.headers.get("Authorization") or ""
        return hmac.compare_digest(got, f"Bearer {token}")

    def _reject_unauthorised(self) -> bool:
        if self._authorised():
            return False
        self._drain_body()
        self._error(401, "missing or invalid bearer token "
                         "(Authorization: Bearer <token>)")
        return True

    def _send_array(self, arr: np.ndarray,
                    extra: dict[str, str] | None = None) -> None:
        """One in-RAM array as ``.npy`` bytes (previews, frame fetches —
        small by construction, unlike full results)."""
        buf = io.BytesIO()
        np.save(buf, np.ascontiguousarray(arr))
        body = buf.getvalue()
        self.send_response(200)
        self.send_header("Content-Type", "application/x-npy")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (extra or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    # -- verbs ----------------------------------------------------------
    def do_GET(self) -> None:
        url = urlparse(self.path)
        path, query = url.path.rstrip("/") or "/", parse_qs(url.query)
        svc = self.service
        if path == "/healthz":
            # plain = cheap liveness; ?ready=1 = degrade-aware
            # readiness via the SLO engine (503 + machine-readable
            # detail while a critical rule fires)
            if (query.get("ready") or ["0"])[0] in ("1", "true"):
                return self._json(*svc.readiness())
            return self._json(200, {"ok": True,
                                    "pending": svc.queue.pending()})
        if path == "/slo":
            return self._json(200, svc.slo_snapshot())
        if path == "/events":
            try:
                since = int((query.get("since") or ["0"])[0])
                raw_limit = (query.get("limit") or [None])[0]
                limit = None if raw_limit is None else int(raw_limit)
            except ValueError:
                return self._error(400, "since/limit must be integers")
            return self._json(200, svc.events.since(since, limit=limit))
        if path == "/cluster":
            if svc.broker is None:
                return self._error(409, "not serving in broker mode")
            return self._json(200, svc.broker.cluster())
        if path == "/stats":
            return self._json(200, svc.stats())
        if path == "/metrics":
            return self._text(200, svc.metrics.render_prometheus(),
                              content_type=MetricsRegistry.CONTENT_TYPE)
        if path == "/plugins":
            return self._json(200, registry_spec())
        if path == "/jobs":
            return self._json(200, {"jobs": svc.queue.snapshot()})
        if path == "/sweeps":
            return self._json(200, {"sweeps": svc.sweeps.snapshot_all()})
        if path == "/workflows":
            return self._json(
                200, {"workflows": svc.workflows.snapshot_all()})
        # trace regex first — _WORKFLOW_RE would also match ".../trace"
        m = _WORKFLOW_TRACE_RE.match(path)
        if m:
            workflow_id = unquote(m.group(1))
            try:
                return self._json(200, svc.workflow_trace(workflow_id))
            except KeyError:
                return self._error(
                    404, f"unknown workflow {workflow_id!r}")
        m = _WORKFLOW_RE.match(path)
        if m:
            workflow_id = unquote(m.group(1))
            try:
                return self._json(200, svc.workflows.status(workflow_id))
            except KeyError:
                return self._error(
                    404, f"unknown workflow {workflow_id!r}")
        m = _SWEEP_RESULT_RE.match(path)
        if m:
            return self._send_sweep_result(
                unquote(m.group(1)), (query.get("dataset") or [None])[0])
        m = _SWEEP_RE.match(path)
        if m:
            sweep_id = unquote(m.group(1))
            try:
                return self._json(200, svc.sweeps.status(sweep_id))
            except KeyError:
                return self._error(404, f"unknown sweep {sweep_id!r}")
        if path == "/workers":
            if svc.broker is None:
                return self._error(409, "not serving in broker mode")
            return self._json(200, svc.broker.stats()["workers"])
        if path == "/executables":
            # token-authed even though it is a read: the hot list and
            # the payloads below are worker-protocol surface, not a
            # public monitoring endpoint
            if self._reject_unauthorised():
                return
            if svc.broker is None:
                return self._error(409, "not serving in broker mode")
            return self._json(200, {"hot": svc.broker.hot_executables()})
        m = _EXEC_RE.match(path)
        if m:
            if self._reject_unauthorised():
                return
            if svc.broker is None:
                return self._error(409, "not serving in broker mode")
            sig = m.group(1)
            try:
                payload = svc.broker.get_executable(sig)
            except KeyError:
                return self._error(404, f"unknown executable {sig!r}")
            self.send_response(200)
            self.send_header("Content-Type", "application/octet-stream")
            self.send_header("Content-Length", str(len(payload)))
            self.send_header("X-Executable-Sig", sig)
            self.end_headers()
            # stream in blocks: payloads can be tens of MB
            for i in range(0, len(payload), 1 << 20):
                self.wfile.write(payload[i:i + (1 << 20)])
            return
        m = _TRACE_RE.match(path)
        if m:
            job_id = unquote(m.group(1))
            fmt = (query.get("format") or [None])[0]
            as_text, as_otlp = fmt == "text", fmt == "otlp"
            try:
                job = svc.queue.job(job_id)
            except KeyError:
                # evicted by max_history?  the trace spool keeps the
                # timeline after the job record is gone
                rec = (svc.trace_spool.get(job_id)
                       if svc.trace_spool is not None else None)
                if rec is None:
                    return self._error(404, f"unknown job {job_id!r}")
                if as_text:
                    spans = []
                    for d in rec.get("spans", ()):
                        try:
                            spans.append(Span.from_wire(d))
                        except (KeyError, TypeError, ValueError):
                            continue
                    return self._text(200, render_gantt(spans) + "\n")
                if as_otlp:
                    return self._json(
                        200, trace_to_otlp(rec, {"job.id": job_id}))
                return self._json(200, rec)
            if as_text:
                return self._text(
                    200, render_gantt(job.trace.spans()) + "\n")
            if as_otlp:
                return self._json(
                    200, trace_to_otlp(job.trace, {"job.id": job_id}))
            return self._json(200, {"job_id": job_id,
                                    **job.trace.to_wire()})
        m = _PREVIEW_RE.match(path)
        if m:
            job_id = unquote(m.group(1))
            try:
                arr, covered = svc.preview(job_id)
            except KeyError:
                return self._error(404, f"unknown job {job_id!r}")
            except (RuntimeError, ValueError) as e:
                return self._error(409, str(e))
            return self._send_array(arr,
                                    extra={"X-Watermark": str(covered)})
        m = _FRAMES_RE.match(path)
        if m:
            return self._fetch_frames(unquote(m.group(1)), query)
        m = _JOB_RE.match(path)
        if m:
            job_id = unquote(m.group(1))
            try:
                return self._json(200, svc.queue.job(job_id).snapshot())
            except KeyError:
                return self._error(404, f"unknown job {job_id!r}")
        m = _RESULT_RE.match(path)
        if m:
            return self._send_result(
                unquote(m.group(1)), (query.get("dataset") or [None])[0])
        self._error(404, f"no route for GET {path}")

    def do_POST(self) -> None:
        if self._reject_unauthorised():
            return
        path = urlparse(self.path).path.rstrip("/")
        m = _FRAMES_RE.match(path)
        if m:
            return self._ingest_frames(unquote(m.group(1)))
        m = _EOF_RE.match(path)
        if m:
            job_id = unquote(m.group(1))
            self._drain_body()            # EOF needs no body
            try:
                return self._json(200, self.service.mark_eof(job_id))
            except KeyError:
                return self._error(404, f"unknown job {job_id!r}")
            except RuntimeError as e:
                return self._error(409, str(e))
        if path == "/jobs":
            return self._submit()
        if path == "/sweeps":
            return self._submit_sweep()
        if path == "/workflows":
            return self._submit_workflow()
        if path == "/workers":
            return self._broker_call(
                lambda b, body: (201, b.register(body)))
        if path == "/jobs/lease":
            return self._broker_call(self._lease)
        m = _PROGRESS_RE.match(path)
        if m:
            job_id = unquote(m.group(1))
            return self._broker_call(
                lambda b, body: (200, b.progress(
                    job_id, self._worker_of(body), body)))
        m = _COMPLETE_RE.match(path)
        if m:
            job_id = unquote(m.group(1))
            return self._broker_call(
                lambda b, body: (200, b.complete(
                    job_id, self._worker_of(body), body)))
        self._drain_body()
        self._error(404, f"no route for POST {self.path}")

    def _submit(self) -> None:
        try:
            envelope = self._read_body()
            job = self.service.submit_envelope(envelope)
        except (WireError, ProcessListError) as e:
            return self._error(400, str(e))
        except QueueFull as e:
            return self._error(429, str(e))
        except ValueError as e:           # duplicate active job id
            return self._error(409, str(e))
        self._json(201, {"job_id": job.job_id, "state": job.state.value,
                         "priority": job.priority})

    def _submit_sweep(self) -> None:
        # NB: SweepError/WireError are ValueError subclasses — they must
        # be caught before the duplicate-id ValueError below
        try:
            envelope = self._read_body()
            group = self.service.submit_sweep(envelope)
        except (SweepError, WireError, ProcessListError) as e:
            return self._error(400, str(e))
        except QueueFull as e:
            return self._error(429, str(e))
        except ValueError as e:           # duplicate active sweep/job id
            return self._error(409, str(e))
        self._json(201, {
            "sweep_id": group.sweep_id, "state": group.state(),
            "n_variants": group.n_variants, "shape": list(group.shape),
            "axes": [a.spec() for a in group.axes],
            "job_ids": [j.job_id for j in group.jobs]})

    def _submit_workflow(self) -> None:
        # NB: WorkflowError/WireError are ValueError subclasses — they
        # must be caught before the duplicate-id ValueError below
        try:
            envelope = self._read_body()
            group = self.service.submit_workflow(envelope)
        except (WorkflowError, WireError, ProcessListError) as e:
            return self._error(400, str(e))
        except QueueFull as e:
            return self._error(429, str(e))
        except ValueError as e:       # duplicate active workflow/job id
            return self._error(409, str(e))
        self._json(201, {
            "workflow_id": group.workflow_id, "state": group.state(),
            "n_nodes": group.n_nodes, "nodes": list(group.nodes),
            "job_ids": [j.job_id for j in group.jobs]})

    # -- streaming ingest (docs/streaming.md) ---------------------------
    def _ingest_frames(self, job_id: str) -> None:
        """POST /jobs/{id}/frames: raw ``.npy`` body + ``X-Start-Frame``
        header → appended to the job's stream buffer."""
        try:
            start = int(self.headers.get("X-Start-Frame", ""))
        except (TypeError, ValueError):
            self._drain_body()
            return self._error(
                400, "POST frames needs an integer X-Start-Frame header")
        length = int(self.headers.get("Content-Length") or 0)
        payload = self.rfile.read(length) if length else b""
        if not payload:
            return self._error(
                400, "empty frames body (raw .npy bytes expected)")
        try:
            frames = np.load(io.BytesIO(payload), allow_pickle=False)
        except ValueError as e:
            return self._error(400, f"frames body is not a valid .npy: "
                                    f"{e}")
        try:
            out = self.service.ingest_frames(job_id, frames, start)
        except KeyError:
            return self._error(404, f"unknown job {job_id!r}")
        except RuntimeError as e:
            return self._error(409, str(e))
        self._json(200, out)

    def _fetch_frames(self, job_id: str, query: dict) -> None:
        """GET /jobs/{id}/frames?start=&max=: how a broker-mode worker
        pulls the buffered stream.  204 (with ``X-EOF``/``X-Watermark``
        headers) when nothing at-or-after ``start`` has arrived yet."""
        svc = self.service
        try:
            job = svc.queue.job(job_id)
        except KeyError:
            return self._error(404, f"unknown job {job_id!r}")
        if not job.streaming:
            return self._error(409, f"job {job_id!r} is not a "
                                    f"streaming job")
        try:
            start = int((query.get("start") or ["0"])[0])
            raw_max = (query.get("max") or [None])[0]
            max_frames = None if raw_max is None else int(raw_max)
        except ValueError:
            return self._error(400, "start/max must be integers")
        st = job.stream
        with st.lock:
            arr, _ = st.fetch(start, max_frames)
            eof, watermark = st.eof, st.watermark
        headers = {"X-Start": str(start),
                   "X-EOF": "1" if eof else "0",
                   "X-Watermark": str(watermark)}
        if arr is None:
            self.send_response(204)
            for k, v in {**headers, "X-Count": "0"}.items():
                self.send_header(k, v)
            self.end_headers()
            return
        self._send_array(arr, extra={**headers,
                                     "X-Count": str(arr.shape[0])})

    # -- worker-pull protocol (broker mode) -----------------------------
    @staticmethod
    def _worker_of(body: Any) -> str:
        wid = body.get("worker_id") if isinstance(body, dict) else None
        if not isinstance(wid, str):
            raise WireError('body must carry a string "worker_id"')
        return wid

    @staticmethod
    def _lease(broker, body: Any) -> tuple[int, Any]:
        wid = _PipelineHandler._worker_of(body)
        max_jobs = body.get("max_jobs", 1)
        if not isinstance(max_jobs, int) or max_jobs < 1:
            raise WireError(f"max_jobs must be a positive int, got "
                            f"{max_jobs!r}")
        timeout = body.get("timeout", 0.0)
        if not isinstance(timeout, (int, float)) or timeout < 0 \
                or timeout > 30:
            raise WireError(f"timeout must be 0..30s, got {timeout!r}")
        prefetched = body.get("prefetched")
        if prefetched is not None and (
                not isinstance(prefetched, int) or prefetched < 0
                or isinstance(prefetched, bool)):
            raise WireError(f"prefetched must be a non-negative int, "
                            f"got {prefetched!r}")
        return 200, {"jobs": broker.lease(
            wid, max_jobs=max_jobs, timeout=float(timeout),
            secret=body.get("worker_secret"), prefetched=prefetched)}

    def _broker_call(self, fn) -> None:
        """Run one worker-protocol operation: parse the JSON body, hand
        it to ``fn(broker, body) -> (status, payload)``, map the shared
        error contract (409 no-broker/lease-lost, 404 unknown, 403 bad
        worker secret, 400 malformed)."""
        if self.service.broker is None:
            self._drain_body()
            return self._error(
                409, "not serving in broker mode (start the service "
                     "with workers_remote=True / --workers-remote)")
        try:
            body = self._read_body()
            code, payload = fn(self.service.broker, body)
        except WireError as e:
            return self._error(400, str(e))
        except WorkerAuthError as e:
            return self._error(403, str(e))
        except LeaseLost as e:
            return self._error(409, str(e))
        except KeyError as e:
            return self._error(404, f"unknown {e}")
        self._json(code, payload)

    def do_PUT(self) -> None:
        """Uploads from a leased worker: raw ``.npy`` result bytes to
        ``/jobs/{id}/result?dataset=name``, or a serialized executable
        to ``/executables/{sig}`` — both identified by ``X-Worker-Id``
        + ``X-Worker-Secret`` headers."""
        if self._reject_unauthorised():
            return
        url = urlparse(self.path)
        m = _EXEC_RE.match(url.path.rstrip("/"))
        if m:
            return self._put_executable(m.group(1))
        m = _RESULT_RE.match(url.path.rstrip("/"))
        if not m:
            self._drain_body()
            return self._error(404, f"no route for PUT {self.path}")
        if self.service.broker is None:
            self._drain_body()
            return self._error(409, "not serving in broker mode")
        job_id = unquote(m.group(1))
        query = parse_qs(url.query)
        dataset = (query.get("dataset") or [None])[0]
        worker_id = self.headers.get("X-Worker-Id")
        if not dataset or not worker_id:
            self._drain_body()
            return self._error(
                400, "PUT result needs ?dataset= and an X-Worker-Id "
                     "header")
        length = int(self.headers.get("Content-Length") or 0)
        payload = self.rfile.read(length) if length else b""
        if not payload:
            return self._error(400, "empty result body")
        try:
            self.service.broker.store_result(
                job_id, worker_id, dataset, payload,
                secret=self.headers.get("X-Worker-Secret"))
        except WireError as e:            # e.g. unsafe dataset name
            return self._error(400, str(e))
        except WorkerAuthError as e:
            return self._error(403, str(e))
        except LeaseLost as e:
            return self._error(409, str(e))
        except KeyError:
            return self._error(404, f"unknown job {job_id!r}")
        self._json(200, {"job_id": job_id, "dataset": dataset,
                         "bytes": len(payload)})

    def _put_executable(self, sig: str) -> None:
        """PUT /executables/{sig}: a worker hands over one serialized
        executable it just compiled (docs/worker-protocol.md)."""
        if self.service.broker is None:
            self._drain_body()
            return self._error(409, "not serving in broker mode")
        worker_id = self.headers.get("X-Worker-Id")
        if not worker_id:
            self._drain_body()
            return self._error(
                400, "PUT executable needs an X-Worker-Id header")
        length = int(self.headers.get("Content-Length") or 0)
        payload = self.rfile.read(length) if length else b""
        if not payload:
            return self._error(400, "empty executable body")
        try:
            out = self.service.broker.put_executable(
                worker_id, self.headers.get("X-Worker-Secret"), sig,
                payload)
        except WireError as e:
            return self._error(400, str(e))
        except WorkerAuthError as e:
            return self._error(403, str(e))
        except KeyError:
            return self._error(404, f"unknown worker {worker_id!r}")
        self._json(200, {**out, "bytes": len(payload)})

    def do_DELETE(self) -> None:
        if self._reject_unauthorised():
            return
        self._drain_body()              # DELETEs may carry a body
        path = urlparse(self.path).path.rstrip("/")
        m = _SWEEP_RE.match(path)
        if m:
            sweep_id = unquote(m.group(1))
            try:
                return self._json(200, self.service.cancel_sweep(sweep_id))
            except KeyError:
                return self._error(404, f"unknown sweep {sweep_id!r}")
        m = _WORKFLOW_RE.match(path)
        if m:
            workflow_id = unquote(m.group(1))
            try:
                return self._json(
                    200, self.service.cancel_workflow(workflow_id))
            except KeyError:
                return self._error(
                    404, f"unknown workflow {workflow_id!r}")
        m = _JOB_RE.match(path)
        if not m:
            return self._error(404, f"no route for DELETE {self.path}")
        job_id = unquote(m.group(1))
        try:
            out = self.service.cancel(job_id)
        except KeyError:
            return self._error(404, f"unknown job {job_id!r}")
        if not out["cancelled"]:
            # dispatched or already terminal: rejected, consistently
            return self._json(409, {**out, "error":
                                    f"job is {out['state']}, not queued"})
        self._json(200, out)

    # -- result streaming -----------------------------------------------
    def _send_result(self, job_id: str, dataset: str | None) -> None:
        svc = self.service
        try:
            remote = svc.result_file(job_id, dataset)
            if remote is not None:        # broker mode: stream the file
                return self._send_result_file(remote[1], remote[0])
            ds, transport = svc.result_dataset(job_id, dataset)
            trace = svc.queue.job(job_id).trace
        except KeyError as e:
            return self._error(404, str(e))
        except RuntimeError as e:
            return self._error(409, str(e))
        with use_trace(trace), trace.span("result.fetch",
                                          dataset=ds.name) as fetch:
            self._stream_result(ds, transport, trace)
        observe_spans(svc.metrics, [s for s in trace.spans()
                                    if s.parent_id == fetch.span_id])

    def _stream_result(self, ds, transport: Transport, trace) -> None:
        """The result as ``.npy``: the device's tail of the job
        (``result.device_wait``), the copy to the host (the transport's
        ``transfer.d2h``), then the header and bytes (``result.send``)."""
        header = _npy_header(ds.shape, ds.dtype)
        backing = ds.backing
        if isinstance(backing, ChunkedFile):
            with trace.span("result.send"):
                self._send_npy_head(header, ds)
                # chunk-row slabs straight off the checkpoint-layer file
                # format: O(slab) RAM however big the volume is
                backing.flush()
                step = backing.chunks[0]
                rest = tuple(slice(0, s) for s in ds.shape[1:])
                for i in range(0, ds.shape[0], step):
                    slab = backing.read(
                        (slice(i, min(i + step, ds.shape[0])),) + rest)
                    self.wfile.write(np.ascontiguousarray(slab).tobytes())
            return
        with trace.span("result.device_wait"):
            jax.block_until_ready(backing)
        arr = np.ascontiguousarray(np.asarray(transport.read(ds)))
        with trace.span("result.send"):
            self._send_npy_head(header, ds)
            self.wfile.write(arr.tobytes())

    def _send_npy_head(self, header: bytes, ds) -> None:
        self.send_response(200)
        self.send_header("Content-Type", "application/x-npy")
        self.send_header("Content-Length", str(len(header) + ds.nbytes))
        self.send_header("X-Dataset", ds.name)
        self.end_headers()
        self.wfile.write(header)

    def _send_sweep_result(self, sweep_id: str,
                           dataset: str | None) -> None:
        """Stream the STACKED sweep result as one ``.npy``: shape
        ``(*grid_shape, *variant_shape)`` — the swept parameter axes are
        the new leading dimension(s) (Savu's tuning dimension), variants
        in C grid order.  One variant is materialised at a time, so RAM
        stays O(variant) however wide the grid is."""
        svc = self.service
        try:
            group, shape, dtype, first = svc.sweeps.result_plan(
                sweep_id, dataset)
        except KeyError as e:
            return self._error(404, str(e))
        except RuntimeError as e:
            return self._error(409, str(e))
        header = _npy_header(shape, dtype)
        body = int(np.prod(shape)) * np.dtype(dtype).itemsize
        self.send_response(200)
        self.send_header("Content-Type", "application/x-npy")
        self.send_header("Content-Length", str(len(header) + body))
        self.send_header("X-Sweep-Id", group.sweep_id)
        self.end_headers()
        self.wfile.write(header)
        self.wfile.write(np.ascontiguousarray(first).tobytes())
        for job in group.jobs[1:]:
            arr = np.ascontiguousarray(svc._variant_array(job.job_id,
                                                          dataset))
            if arr.shape != first.shape or arr.dtype != first.dtype:
                # headers are gone — abort the stream rather than ship
                # a silently corrupt stack (identical chains make this
                # unreachable in practice)
                raise RuntimeError(
                    f"sweep {sweep_id!r}: variant {job.job_id!r} shape/"
                    f"dtype {arr.shape}/{arr.dtype} != "
                    f"{first.shape}/{first.dtype}")
            self.wfile.write(arr.tobytes())

    def _send_result_file(self, path: str, dataset: str | None) -> None:
        """Stream a worker-delivered ``.npy`` file block-wise (broker
        mode) — O(block) RAM, same contract as the chunk-slab path."""
        size = os.path.getsize(path)
        self.send_response(200)
        self.send_header("Content-Type", "application/x-npy")
        self.send_header("Content-Length", str(size))
        if dataset:
            self.send_header("X-Dataset", dataset)
        self.end_headers()
        with open(path, "rb") as fh:
            while True:
                block = fh.read(1 << 20)
                if not block:
                    break
                self.wfile.write(block)
