"""Multi-dataset pipeline service driver — the paper's headline claim
("simultaneous processing of multiple ... datasets") as a running
service: submit N tomography jobs, process them over shared workers with
one compiled-plugin cache, report per-job status and aggregate
throughput, and verify every reconstruction against a serial
``PluginRunner`` reference.

Three modes:

* **demo** (default) — submit ``--jobs`` synthetic scans in-process,
  drain, verify::

      PYTHONPATH=src python -m repro.launch.pipeline_serve --jobs 4
      PYTHONPATH=src python -m repro.launch.pipeline_serve --jobs 8 \\
          --workers 4 --batch --transport sharded

* **server** — bind the JSON-over-HTTP front end and serve until
  interrupted (see ``docs/service.md``)::

      PYTHONPATH=src python -m repro.launch.pipeline_serve --serve 8973

* **client** — talk to a running server::

      PYTHONPATH=src python -m repro.launch.pipeline_serve client \\
          --url http://127.0.0.1:8973 submit --demo-chain --wait

  including parameter sweeps (Savu's parameter tuning — the service
  gang-batches the variants and serves the stacked result; see
  ``docs/sweeps.md``)::

      PYTHONPATH=src python -m repro.launch.pipeline_serve client \\
          sweep --demo-chain --param sinogram_filter.cutoff=0.4:1.0:7 \\
          --metric sharpness --wait --out sweep.npy

  workflow DAGs — jobs that depend on jobs, one atomic spec-v3
  envelope (``docs/workflows.md``)::

      PYTHONPATH=src python -m repro.launch.pipeline_serve client \\
          workflow --demo --wait

  and live streaming acquisition (``docs/streaming.md``) — submit a
  v2 streaming job, feed frames as they "arrive", peek at the partial
  reconstruction before EOF::

      PYTHONPATH=src python -m repro.launch.pipeline_serve client \\
          submit --demo-chain --streaming --job-id scan0
      PYTHONPATH=src python -m repro.launch.pipeline_serve client \\
          ingest scan0 --synthetic --chunk 8 --rate 4
      PYTHONPATH=src python -m repro.launch.pipeline_serve client \\
          preview scan0 --out live.npy

  plus the cluster health plane (``docs/observability.md``) — SLO rule
  states, the structured event log (tail with ``--follow``), the
  per-worker scoreboard::

      PYTHONPATH=src python -m repro.launch.pipeline_serve client slo
      PYTHONPATH=src python -m repro.launch.pipeline_serve client \\
          events --follow --format text
      PYTHONPATH=src python -m repro.launch.pipeline_serve client \\
          cluster --format text

* **multi-host demo** — ``--workers-remote N`` runs the broker and N
  detached worker *subprocesses* pulling jobs from it over HTTP (one
  queue, many worker processes — see ``docs/worker-protocol.md``)::

      PYTHONPATH=src python -m repro.launch.pipeline_serve \\
          --jobs 6 --workers-remote 2 --checkpoint-dir /tmp/ckpts

  ``--serve PORT --workers-remote N`` serves the broker for external
  workers too (N may be 0; start more with
  ``python -m repro.service.worker --url ...``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import jax
from jax.sharding import Mesh

from ..core import (ChunkedFileTransport, InMemoryTransport, PluginRunner,
                    ShardedTransport)
from ..service import (METRICS, CheckpointStore, CompileCache, JobQueue,
                       PipelineClient, PipelineScheduler, PipelineService,
                       ServiceError, to_spec)
from ..service.compile_cache import setup_compilation_cache
from ..service.worker import spawn_local_workers
from ..tomo import standard_chain

_EPILOG = """\
transport notes:
  --transport chunked   every dataset lives in a chunk-addressed file
                        (RAM is O(frames), never O(dataset)); with
                        --checkpoint-dir the checkpointer HARD-LINKS
                        those chunk files and writes only dirty-chunk
                        increments, so per-step checkpoints are cheap
                        (see docs/checkpoint-format.md)
  --transport sharded   jit-compiled plugins on the device mesh, with
                        the process-level compile cache

scheduling notes:
  --batch gangs queued jobs with identical chain signatures: each
  plugin step runs as ONE compiled call over all gang members, driven
  by the single worker that popped the gang — so for identical-chain
  workloads --workers does NOT multiply gang throughput; extra workers
  only help when distinct chains (or resumed jobs, which always step
  solo) are mixed in.  --batch also disables buffer donation on the
  sharded transport (stacked gang inputs outlive the call).
"""


def _chain(args, seed: int):
    return standard_chain(n_det=args.n_det, n_angles=args.n_angles,
                          n_rows=args.n_rows, seed=seed,
                          use_pallas=args.pallas)


def _build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="repro.launch.pipeline_serve",
        description=__doc__.split("\n\n")[0],
        epilog=_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--jobs", type=int, default=4,
                    help="demo mode: number of synthetic scans to submit")
    ap.add_argument("--workers", type=int, default=2,
                    help="scheduler worker threads (see scheduling notes "
                         "below for the --batch interaction)")
    ap.add_argument("--transport", default="sharded",
                    choices=("sharded", "inmemory", "chunked"),
                    help="execution transport (see transport notes below)")
    ap.add_argument("--batch", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="gang identical chains into one compiled call "
                         "per plugin step (ganged steps run under a "
                         "single worker; see scheduling notes)")
    ap.add_argument("--fuse", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="fuse consecutive linear plugins into one jit")
    ap.add_argument("--verify", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="demo mode: compare each job against a serial "
                         "PluginRunner")
    ap.add_argument("--pallas", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--n-det", type=int, default=48)
    ap.add_argument("--n-angles", type=int, default=48)
    ap.add_argument("--n-rows", type=int, default=2)
    ap.add_argument("--max-pending", type=int, default=64,
                    help="admission bound: submissions past this many "
                         "non-terminal jobs get QueueFull / HTTP 429")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="persist per-plugin checkpoints here; a killed "
                         "job resubmitted with the same id resumes at "
                         "the last finished plugin")
    ap.add_argument("--serve", type=int, metavar="PORT", default=None,
                    help="serve the HTTP front end on PORT instead of "
                         "running the demo (POST /jobs, GET /jobs/{id}, "
                         "GET /jobs/{id}/result, GET /stats, ...)")
    ap.add_argument("--host", default="127.0.0.1",
                    help="bind address for --serve")
    ap.add_argument("--max-history", type=int, default=256,
                    help="--serve: retained terminal jobs (older results "
                         "are evicted)")
    ap.add_argument("--batch-max", type=int, default=4,
                    help="--batch: gang size bound")
    ap.add_argument("--workers-remote", type=int, default=None,
                    metavar="N",
                    help="broker mode: spawn N worker SUBPROCESSES "
                         "pulling jobs over HTTP (demo), or serve the "
                         "broker for external workers (--serve; N may "
                         "be 0)")
    ap.add_argument("--lease-ttl", type=float, default=15.0,
                    help="broker mode: seconds a lease survives "
                         "without a worker heartbeat before the job is "
                         "requeued")
    ap.add_argument("--shared-fs", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="broker mode: workers write results straight "
                         "into the broker's results_dir instead of "
                         "uploading over HTTP")
    ap.add_argument("--token", default=None,
                    help="--serve: require this bearer token on every "
                         "mutating request (Authorization: Bearer ...); "
                         "spawned workers get it automatically")
    ap.add_argument("--trace-spool", default=None, metavar="DIR",
                    help="--serve: spool evicted terminal-job traces "
                         "to this directory (bounded ring; "
                         "docs/observability.md)")
    return ap


def _transport_factory(args, cache: CompileCache):
    if args.transport == "sharded":
        mesh = Mesh(np.asarray(jax.devices()), ("data",))
        # gang batching stacks job inputs — donation would invalidate
        # buffers the stack still references.  Checkpointing no longer
        # forces donation off: the runner's liveness analysis donates a
        # buffer only at its FINAL use, so every dataset a checkpoint
        # (or a branching chain) still needs stays alive.
        donate = not args.batch
        return lambda job: ShardedTransport(mesh, donate=donate,
                                            compile_cache=cache)
    if args.transport == "chunked":
        return lambda job: ChunkedFileTransport()
    return lambda job: InMemoryTransport()


# ----------------------------------------------------------------------
def _serve_main(args) -> None:
    workers = []
    if args.workers_remote is not None:       # broker mode
        service = PipelineService(
            workers_remote=True, max_pending=args.max_pending,
            max_history=args.max_history, lease_ttl=args.lease_ttl,
            token=args.token, trace_spool=args.trace_spool)
        host, port = service.serve(host=args.host, port=args.serve,
                                   block=False)
        workers = spawn_local_workers(
            f"http://{host}:{port}", args.workers_remote,
            transport=args.transport,
            checkpoint_dir=args.checkpoint_dir,
            shared_fs=args.shared_fs, token=args.token)
        print(f"pipeline broker listening on http://{host}:{port}  "
              f"({len(workers)} local worker processes, lease_ttl="
              f"{args.lease_ttl}s; attach more with `python -m "
              f"repro.service.worker --url http://{host}:{port}`)",
              flush=True)
    else:
        cache = CompileCache()
        checkpoints = (CheckpointStore(args.checkpoint_dir)
                       if args.checkpoint_dir else None)
        service = PipelineService(
            transport_factory=_transport_factory(args, cache),
            n_workers=args.workers, max_pending=args.max_pending,
            max_history=args.max_history, checkpoints=checkpoints,
            batch_identical=args.batch, batch_max=args.batch_max,
            fuse=args.fuse, compile_cache=cache,
            token=args.token, trace_spool=args.trace_spool)
        host, port = service.serve(host=args.host, port=args.serve,
                                   block=False)
        print(f"pipeline service listening on http://{host}:{port}  "
              f"({args.workers} workers, transport={args.transport}"
              f"{', gang-batched' if args.batch else ''}"
              f"{', checkpointed' if checkpoints else ''})", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        print("shutting down")
    finally:
        for p in workers:
            p.terminate()
        service.stop()


# ----------------------------------------------------------------------
def _remote_demo(args) -> None:
    """The multi-host demo: one queue, N worker processes.  Submit
    ``--jobs`` scans over HTTP, let the worker subprocesses pull them,
    verify every reconstruction against a serial PluginRunner."""
    service = PipelineService(
        workers_remote=True, max_pending=max(args.max_pending, args.jobs),
        lease_ttl=args.lease_ttl)
    host, port = service.serve(port=0)
    url = f"http://{host}:{port}"
    workers = spawn_local_workers(
        url, args.workers_remote, transport=args.transport,
        checkpoint_dir=args.checkpoint_dir, shared_fs=args.shared_fs)
    client = PipelineClient(url)
    try:
        t0 = time.time()
        ids = [client.submit(_chain(args, seed=i), job_id=f"tomo-{i:03d}",
                             metadata={"seed": i})
               for i in range(args.jobs)]
        snaps = [client.wait(jid, timeout=600) for jid in ids]
        wall = time.time() - t0
        for s in snaps:
            extra = (f" (resumed at plugin {s['resumed_from']})"
                     if s["resumed_from"] else "")
            print(f"  {s['job_id']}: {s['status']:>10s}  "
                  f"worker={s['worker_id']}  wall={s['wall']:.2f}s{extra}")
        failed = [s for s in snaps if s["state"] != "done"]
        if failed:
            for s in failed:
                print(s["error"])
            raise SystemExit(f"{len(failed)}/{len(snaps)} jobs failed")
        got = ({s["job_id"]: client.result(s["job_id"]) for s in snaps}
               if args.verify else {})
        st = client.stats()
        per_worker = {w: s["jobs_done"]
                      for w, s in st["workers"].items()}
        print(f"{args.jobs} jobs in {wall:.2f}s -> "
              f"{args.jobs / wall:.2f} jobs/s  "
              f"({args.workers_remote} worker processes, "
              f"transport={args.transport})")
        print(f"per-worker jobs done: {per_worker}  "
              f"requeues: {st['jobs_requeued']}")
    finally:
        for p in workers:
            p.terminate()
        for p in workers:
            p.wait(timeout=10)
        service.stop()
    if args.verify:
        # only now, with the workers gone, may this process take the
        # device for the reference runs (one process per chip)
        worst = 0.0
        for s in snaps:
            ref = PluginRunner(_chain(args, seed=s["metadata"]["seed"])).run()
            want = np.asarray(ref["recon"].materialise())
            np.testing.assert_allclose(got[s["job_id"]], want, rtol=1e-3,
                                       atol=1e-4)
            worst = max(worst, float(np.max(np.abs(got[s["job_id"]]
                                                    - want))))
        print(f"verified {len(snaps)} reconstructions against "
              f"serial PluginRunner (max |Δ|={worst:.2e})")


# ----------------------------------------------------------------------
def _demo_main(args) -> None:
    cache = CompileCache()
    factory = _transport_factory(args, cache)
    queue = JobQueue(max_pending=args.max_pending)
    checkpoints = (CheckpointStore(args.checkpoint_dir)
                   if args.checkpoint_dir else None)
    sched = PipelineScheduler(
        queue, transport_factory=factory, n_workers=args.workers,
        checkpoints=checkpoints, batch_identical=args.batch,
        batch_max=max(args.batch_max, args.jobs), fuse=args.fuse,
        compile_cache=cache)

    jobs = [queue.submit(_chain(args, seed=i), priority=0,
                         job_id=f"tomo-{i:03d}", metadata={"seed": i})
            for i in range(args.jobs)]
    t0 = time.time()
    sched.start()
    ok = sched.drain(timeout=600)
    wall = time.time() - t0
    sched.shutdown()
    if not ok:
        raise SystemExit("timed out waiting for jobs")

    failed = [j for j in jobs if j.state.value != "done"]
    for j in jobs:
        extra = (f" (resumed at plugin {j.resumed_from})"
                 if j.resumed_from else "")
        print(f"  {j.job_id}: {j.status:>10s}  wall={j.wall:.2f}s{extra}")
    if failed:
        for j in failed:
            print(j.metadata.get("traceback", j.error))
        raise SystemExit(f"{len(failed)}/{len(jobs)} jobs failed")

    if args.verify:
        worst = 0.0
        for j in jobs:
            ref = PluginRunner(_chain(args, seed=j.metadata["seed"])).run()
            got = j.runner.transport.read(j.runner.datasets["recon"])
            want = np.asarray(ref["recon"].materialise())
            np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)
            worst = max(worst, float(np.max(np.abs(got - want))))
        print(f"verified {len(jobs)} reconstructions against serial "
              f"PluginRunner (max |Δ|={worst:.2e})")

    st = sched.stats()
    print(f"{len(jobs)} jobs in {wall:.2f}s -> {len(jobs) / wall:.2f} "
          f"jobs/s  ({args.workers} workers, transport={args.transport}"
          f"{', gang-batched' if args.batch else ''})")
    print(f"compile cache: {cache.stats()}")
    if st.get("gangs_run"):
        print(f"gangs executed: {st['gangs_run']}")


# ----------------------------------------------------------------------
def _client_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="repro.launch.pipeline_serve client",
        description="Talk to a running pipeline service over HTTP.")
    ap.add_argument("--url", default="http://127.0.0.1:8973",
                    help="service base URL")
    ap.add_argument("--token", default=None,
                    help="bearer token for a token-armed service")
    sub = ap.add_subparsers(dest="action", required=True)

    s = sub.add_parser("submit", help="POST a process list")
    s.add_argument("--spec", metavar="FILE", default=None,
                   help="spec v1 JSON file (see docs/plugin-spec.md)")
    s.add_argument("--demo-chain", action="store_true",
                   help="submit the standard synthetic chain instead of "
                        "a spec file")
    s.add_argument("--streaming", action="store_true",
                   help="submit as a v2 STREAMING job: the loader's "
                        "frames arrive over `client ingest`, not from "
                        "the spec (docs/streaming.md)")
    s.add_argument("--n-det", type=int, default=48)
    s.add_argument("--n-angles", type=int, default=48)
    s.add_argument("--n-rows", type=int, default=2)
    s.add_argument("--seed", type=int, default=0)
    s.add_argument("--priority", type=int, default=0)
    s.add_argument("--job-id", default=None)
    s.add_argument("--wait", action="store_true",
                   help="poll until the job is terminal")

    ing = sub.add_parser(
        "ingest", help="stream frames into a streaming job "
                       "(docs/streaming.md)",
        description="POST frame slabs to a v2 streaming job in arrival "
                    "order, optionally rate-limited, then mark EOF.")
    ing.add_argument("job_id")
    ing.add_argument("--npy", metavar="FILE", default=None,
                     help=".npy frame stack (axis 0 = arrival axis)")
    ing.add_argument("--synthetic", action="store_true",
                     help="generate the standard synthetic scan's raw "
                          "frames (must match the submitted chain's "
                          "--n-det/--n-angles/--n-rows/--seed)")
    ing.add_argument("--n-det", type=int, default=48)
    ing.add_argument("--n-angles", type=int, default=48)
    ing.add_argument("--n-rows", type=int, default=2)
    ing.add_argument("--seed", type=int, default=0)
    ing.add_argument("--chunk", type=int, default=8,
                     help="frames per POST")
    ing.add_argument("--rate", type=float, default=0.0, metavar="HZ",
                     help="chunk posts per second (0 = full speed)")
    ing.add_argument("--start", type=int, default=0,
                     help="index of the first frame being sent (resume "
                          "an interrupted feed from the watermark)")
    ing.add_argument("--eof", action=argparse.BooleanOptionalAction,
                     default=True,
                     help="post EOF after the last chunk (--no-eof to "
                          "keep the stream open)")

    pv = sub.add_parser(
        "preview", help="download the current partial reconstruction")
    pv.add_argument("job_id")
    pv.add_argument("--out", metavar="FILE", default=None,
                    help="write the npy here (default: "
                         "<job_id>-preview.npy)")

    sw = sub.add_parser(
        "sweep", help="POST a parameter sweep (docs/sweeps.md)",
        description="Expand a process list over a ≤2-param grid of "
                    "sweepable values; the service gang-batches the "
                    "variants and serves the stacked result.")
    sw.add_argument("--spec", metavar="FILE", default=None,
                    help="spec v1 JSON file (see docs/plugin-spec.md)")
    sw.add_argument("--demo-chain", action="store_true",
                    help="sweep the standard synthetic chain")
    sw.add_argument("--n-det", type=int, default=48)
    sw.add_argument("--n-angles", type=int, default=48)
    sw.add_argument("--n-rows", type=int, default=2)
    sw.add_argument("--seed", type=int, default=0)
    sw.add_argument("--param", action="append", required=True,
                    metavar="PLUGIN.PARAM=SPEC", dest="params",
                    help="one sweep axis (repeatable, ≤2): SPEC is "
                         "START:STOP:N (inclusive linspace, e.g. "
                         "sinogram_filter.cutoff=0.4:1.0:7) or a "
                         "comma list of JSON values (e.g. "
                         "ring_removal.strength=0.5,1.0,1.5); PLUGIN "
                         "is a wire name or an entry index")
    sw.add_argument("--metric", default=None, choices=sorted(METRICS),
                    help="score each variant and report best_variant")
    sw.add_argument("--priority", type=int, default=0)
    sw.add_argument("--sweep-id", default=None)
    sw.add_argument("--wait", action="store_true",
                    help="poll until every variant is terminal")
    sw.add_argument("--out", metavar="FILE", default=None,
                    help="download the stacked npy here when done "
                         "(implies --wait)")

    wf = sub.add_parser(
        "workflow", help="POST a workflow DAG (docs/workflows.md)",
        description="Submit a DAG of process lists as ONE spec-v3 "
                    "envelope: nodes depend on nodes (`after` + "
                    "upstream-output references), admitted atomically "
                    "— a cycle or dangling reference rejects the whole "
                    "request with nothing enqueued.")
    wf.add_argument("--envelope", metavar="FILE", default=None,
                    help="JSON file: a full v3 envelope or a bare "
                         "{node: {process_list, after}} mapping")
    wf.add_argument("--demo", action="store_true",
                    help="submit the 3-stage demo DAG instead: "
                         "recon -> downsample -> quantify")
    wf.add_argument("--n-det", type=int, default=48)
    wf.add_argument("--n-angles", type=int, default=48)
    wf.add_argument("--n-rows", type=int, default=2)
    wf.add_argument("--seed", type=int, default=0)
    wf.add_argument("--priority", type=int, default=0)
    wf.add_argument("--workflow-id", default=None)
    wf.add_argument("--wait", action="store_true",
                    help="poll until every node is terminal")
    wfs = sub.add_parser("workflow-status",
                         help="GET one workflow's per-node snapshot")
    wfs.add_argument("workflow_id")
    wft = sub.add_parser(
        "workflow-trace",
        help="GET the workflow-level linked trace (per-node spans + "
             "DAG edges)")
    wft.add_argument("workflow_id")
    wfc = sub.add_parser("workflow-cancel",
                         help="DELETE a workflow (cancel live nodes; "
                              "downstream cones cascade)")
    wfc.add_argument("workflow_id")
    sub.add_parser("workflows", help="GET every workflow's summary")

    sws = sub.add_parser("sweep-status", help="GET one sweep's snapshot")
    sws.add_argument("sweep_id")
    swr = sub.add_parser("sweep-result",
                         help="download the stacked result (.npy)")
    swr.add_argument("sweep_id")
    swr.add_argument("--dataset", default=None)
    swr.add_argument("--out", metavar="FILE", default=None,
                     help="write the npy here (default: <sweep_id>.npy)")
    swc = sub.add_parser("sweep-cancel",
                         help="DELETE a sweep (cancel live variants)")
    swc.add_argument("sweep_id")
    sub.add_parser("sweeps", help="GET every sweep group's summary")

    st = sub.add_parser("status", help="GET one job's snapshot")
    st.add_argument("job_id")
    w = sub.add_parser("wait", help="poll a job to completion")
    w.add_argument("job_id")
    w.add_argument("--timeout", type=float, default=600.0)
    r = sub.add_parser("result", help="download an output dataset (.npy)")
    r.add_argument("job_id")
    r.add_argument("--dataset", default=None)
    r.add_argument("--out", metavar="FILE", default=None,
                   help="write the npy here (default: <job_id>.npy)")
    cx = sub.add_parser("cancel", help="DELETE a queued job")
    cx.add_argument("job_id")
    tr = sub.add_parser(
        "trace", help="GET a job's cross-process span timeline",
        description="Print the job's distributed trace "
                    "(docs/observability.md) — by default as an ASCII "
                    "gantt over every span the broker/scheduler and "
                    "workers recorded.")
    tr.add_argument("job_id")
    tr.add_argument("--json", action="store_true",
                    help="print the raw span list instead of the gantt")
    tr.add_argument("--otlp", action="store_true",
                    help="print the OTLP-shaped JSON export instead "
                         "(?format=otlp; docs/observability.md)")
    slo = sub.add_parser(
        "slo", help="GET the SLO rule states (/slo)",
        description="Every SLO rule's definition, current reading and "
                    "alert lifecycle state (docs/observability.md).")
    slo.add_argument("--format", choices=("json", "text"),
                     default="json")
    ev = sub.add_parser(
        "events", help="GET the structured event log (/events)",
        description="Page — or --follow tail — the bounded structured "
                    "event log: one record per job state transition "
                    "and alert edge, each carrying trace_id / job_id "
                    "/ worker_id (docs/observability.md).")
    ev.add_argument("--since", type=int, default=0,
                    help="resume cursor: only records with seq > N")
    ev.add_argument("--limit", type=int, default=None,
                    help="page size bound")
    ev.add_argument("--follow", action="store_true",
                    help="poll forever, printing records as they land "
                         "(one line each)")
    ev.add_argument("--interval", type=float, default=1.0,
                    help="--follow poll period in seconds")
    ev.add_argument("--format", choices=("json", "text"),
                    default="json")
    cl = sub.add_parser(
        "cluster", help="GET the per-worker scoreboard (/cluster)",
        description="Broker mode: every registered worker's heartbeat "
                    "staleness, active leases with time-to-expiry, "
                    "last error and warm-pool prefetch count.")
    cl.add_argument("--format", choices=("json", "text"),
                    default="json")
    sub.add_parser("jobs", help="GET every job's snapshot")
    sub.add_parser("stats", help="GET scheduler + compile-cache stats")
    sub.add_parser("metrics",
                   help="GET the Prometheus text exposition (/metrics)")
    sub.add_parser("plugins", help="GET the wire-format plugin registry")
    return ap


def _parse_sweep_axis(s: str) -> dict:
    """``PLUGIN.PARAM=START:STOP:N`` (inclusive linspace) or
    ``PLUGIN.PARAM=v1,v2,...`` (JSON values) -> one sweep-axis object."""
    target, eq, spec = s.partition("=")
    plugin, dot, param = target.rpartition(".")
    if not (eq and dot and plugin and param and spec):
        raise SystemExit(f"--param wants PLUGIN.PARAM=SPEC, got {s!r}")
    if ":" in spec and "," not in spec:
        parts = spec.split(":")
        try:
            start, stop, n = (float(parts[0]), float(parts[1]),
                              int(parts[2]))
        except (IndexError, ValueError):
            # a typo like 0.4:1.0 must die here, not as N failed jobs
            raise SystemExit(f"--param range must be START:STOP:N, "
                             f"got {spec!r}") from None
        if len(parts) != 3:
            raise SystemExit(f"--param range must be START:STOP:N, "
                             f"got {spec!r}")
        values = [float(v) for v in np.linspace(start, stop, n)]
    else:
        values = []
        for v in spec.split(","):
            try:
                values.append(json.loads(v))
            except json.JSONDecodeError:
                values.append(v)           # bare string value
    axis: dict = {"param": param, "values": values}
    if plugin.isdigit():
        axis["plugin_index"] = int(plugin)
    else:
        axis["plugin"] = plugin
    return axis


def _demo_workflow(args) -> dict:
    """The 3-stage demo DAG — recon -> downsample -> quantify, the
    downstream nodes fed by upstream outputs (docs/workflows.md)."""
    from ..core.process_list import ProcessList
    from ..tomo import Downsample, HDF5LikeSaver, Quantify, UpstreamLoader
    down = ProcessList()
    down.add(UpstreamLoader,
             params={"data": {"from_job": "recon", "dataset": "recon"}},
             out_datasets=("vol",))
    down.add(Downsample, params={"factor": 2},
             in_datasets=("vol",), out_datasets=("small",))
    down.add(HDF5LikeSaver, in_datasets=("small",))
    quant = ProcessList()
    quant.add(UpstreamLoader,
              params={"data": {"from_job": "downsample",
                               "dataset": "small"}},
              out_datasets=("vol",))
    quant.add(Quantify, in_datasets=("vol",), out_datasets=("stats",))
    quant.add(HDF5LikeSaver, in_datasets=("stats",))
    return {
        "recon": {"process_list": to_spec(standard_chain(
            n_det=args.n_det, n_angles=args.n_angles,
            n_rows=args.n_rows, seed=args.seed))},
        "downsample": {"process_list": to_spec(down)},
        # the upstream reference already implies this edge; the
        # explicit `after` just demonstrates the envelope field
        "quantify": {"process_list": to_spec(quant),
                     "after": ["downsample"]},
    }


def _workflow_main(client: PipelineClient, args) -> None:
    if args.envelope:
        with open(args.envelope) as fh:
            doc = json.load(fh)
        # accept a full v3 envelope or a bare node mapping
        nodes = doc.get("workflow", doc) if isinstance(doc, dict) else doc
    elif args.demo:
        nodes = _demo_workflow(args)
    else:
        raise SystemExit("workflow needs --envelope FILE or --demo")
    reply = client.workflow(nodes, workflow_id=args.workflow_id,
                            priority=args.priority)
    print(json.dumps(reply, indent=2))
    if args.wait:
        snap = client.wait_workflow(reply["workflow_id"])
        print(json.dumps(snap, indent=2))


def _ingest_main(client: PipelineClient, args) -> None:
    """Feed a frame stack into a streaming job chunk by chunk."""
    if args.npy:
        frames = np.load(args.npy)
    elif args.synthetic:
        # materialise exactly what the submitted chain's loader
        # declares, so the streamed run is bit-identical to batch
        pl = standard_chain(n_det=args.n_det, n_angles=args.n_angles,
                            n_rows=args.n_rows, seed=args.seed)
        entry = pl.entries[0]
        loader = entry.cls(**entry.params,
                           in_datasets=list(entry.in_datasets),
                           out_datasets=list(entry.out_datasets))
        frames = np.asarray(loader.load()[0].materialise())
    else:
        raise SystemExit("ingest needs --npy FILE or --synthetic")
    start = args.start
    for lo in range(0, frames.shape[0], args.chunk):
        chunk = frames[lo:lo + args.chunk]
        reply = client.ingest(args.job_id, chunk, start)
        start = reply["watermark"]
        print(f"  fed frames [{reply['start']}, "
              f"{reply['start'] + reply['count']}) -> watermark "
              f"{start}", flush=True)
        if args.rate > 0:
            time.sleep(1.0 / args.rate)
    if args.eof:
        print(json.dumps(client.eof(args.job_id), indent=2))


def _table(rows: list[tuple]) -> str:
    """Plain-text column alignment for the --format text views."""
    widths = [max(len(str(r[i])) for r in rows)
              for i in range(len(rows[0]))]
    return "\n".join(
        "  ".join(str(c).ljust(w) for c, w in zip(r, widths)).rstrip()
        for r in rows)


def _slo_text(snap: dict) -> str:
    rows = [("RULE", "STATE", "VALUE", "THRESHOLD", "FIRED",
             "RESOLVED", "METRIC")]
    for r in snap["rules"]:
        value = "-" if r["value"] is None else f"{r['value']:.3f}"
        rows.append((("*" if r["critical"] else " ") + r["name"],
                     r["state"], value,
                     f"{r['op']} {r['threshold']:g}",
                     r["fired"], r["resolved"], r["metric"]))
    firing = ", ".join(snap["firing"]) or "none"
    return (_table(rows)
            + f"\nfiring: {firing}   (* = critical rule)")


def _event_line(rec: dict) -> str:
    attrs = " ".join(f"{k}={v}"
                     for k, v in sorted(rec["attrs"].items()))
    return (f"{rec['seq']:>6d}  {rec['ts']:.3f}  {rec['event']:<14s} "
            f"trace={rec['trace_id'] or '-'} "
            f"job={rec['job_id'] or '-'} "
            f"worker={rec['worker_id'] or '-'}"
            + (f"  {attrs}" if attrs else ""))


def _cluster_text(doc: dict) -> str:
    rows = [("WORKER", "LEASES", "STALE_S", "DONE", "FAILED",
             "PREFETCHED", "LAST_ERROR")]
    for w in doc["workers"]:
        leases = ",".join(ls["job_id"] for ls in w["leases"]) or "-"
        err = w.get("last_error") or "-"
        if len(err) > 40:
            err = err[:37] + "..."
        rows.append((w["worker_id"], leases,
                     f"{w['heartbeat_staleness_s']:.1f}",
                     w["jobs_done"], w["jobs_failed"],
                     w["prefetched"], err))
    return (_table(rows)
            + f"\nactive_leases={doc['active_leases']}  "
              f"leases_expired={doc['leases_expired']}  "
              f"jobs_requeued={doc['jobs_requeued']}  "
              f"lease_ttl={doc['lease_ttl']}")


def _events_main(client: PipelineClient, args) -> None:
    """One page of the event log, or --follow: tail it forever."""
    if not args.follow:
        page = client.events(since=args.since, limit=args.limit)
        if args.format == "text":
            for rec in page["events"]:
                print(_event_line(rec))
            tail = f"# cursor {page['cursor']}"
            if page["dropped"]:
                tail += f"  ({page['dropped']} dropped before cursor)"
            print(tail)
        else:
            print(json.dumps(page, indent=2))
        return
    cursor = args.since
    try:
        while True:
            page = client.events(since=cursor, limit=args.limit)
            for rec in page["events"]:
                print(_event_line(rec) if args.format == "text"
                      else json.dumps(rec), flush=True)
            cursor = page["cursor"]
            if not page["events"]:
                time.sleep(max(args.interval, 0.05))
    except KeyboardInterrupt:
        pass


def _client_main(argv: list[str]) -> None:
    args = _client_parser().parse_args(argv)
    client = PipelineClient(args.url, token=args.token)
    try:
        if args.action == "sweep":
            if args.spec:
                with open(args.spec) as fh:
                    spec = json.load(fh)
            elif args.demo_chain:
                spec = to_spec(standard_chain(
                    n_det=args.n_det, n_angles=args.n_angles,
                    n_rows=args.n_rows, seed=args.seed))
            else:
                raise SystemExit("sweep needs --spec FILE or --demo-chain")
            reply = client.sweep(
                spec, [_parse_sweep_axis(p) for p in args.params],
                metric=args.metric, priority=args.priority,
                sweep_id=args.sweep_id)
            print(json.dumps(reply, indent=2))
            if args.wait or args.out:
                snap = client.wait_sweep(reply["sweep_id"])
                print(json.dumps(snap, indent=2))
                if args.out and snap["state"] == "done":
                    arr = client.sweep_result(reply["sweep_id"])
                    np.save(args.out, arr)
                    print(f"{args.out}: shape={arr.shape} "
                          f"dtype={arr.dtype}")
        elif args.action == "sweep-status":
            print(json.dumps(client.sweep_status(args.sweep_id),
                             indent=2))
        elif args.action == "sweep-result":
            arr = client.sweep_result(args.sweep_id,
                                      dataset=args.dataset)
            out = args.out or f"{args.sweep_id}.npy"
            np.save(out, arr)
            print(f"{out}: shape={arr.shape} dtype={arr.dtype}")
        elif args.action == "sweep-cancel":
            print(json.dumps(client.cancel_sweep(args.sweep_id),
                             indent=2))
        elif args.action == "sweeps":
            print(json.dumps(client.sweeps(), indent=2))
        elif args.action == "workflow":
            _workflow_main(client, args)
        elif args.action == "workflow-status":
            print(json.dumps(client.workflow_status(args.workflow_id),
                             indent=2))
        elif args.action == "workflow-trace":
            print(json.dumps(client.workflow_trace(args.workflow_id),
                             indent=2))
        elif args.action == "workflow-cancel":
            print(json.dumps(client.cancel_workflow(args.workflow_id),
                             indent=2))
        elif args.action == "workflows":
            print(json.dumps(client.workflows(), indent=2))
        elif args.action == "submit":
            if args.spec:
                with open(args.spec) as fh:
                    spec = json.load(fh)
            elif args.demo_chain:
                spec = to_spec(standard_chain(
                    n_det=args.n_det, n_angles=args.n_angles,
                    n_rows=args.n_rows, seed=args.seed))
            else:
                raise SystemExit("submit needs --spec FILE or --demo-chain")
            if args.streaming:
                spec = {**spec, "version": 2, "streaming": True}
            job_id = client.submit(spec, priority=args.priority,
                                   job_id=args.job_id)
            print(job_id)
            if args.wait:
                print(json.dumps(client.wait(job_id), indent=2))
        elif args.action == "ingest":
            _ingest_main(client, args)
        elif args.action == "preview":
            arr, cut = client.preview(args.job_id)
            out = args.out or f"{args.job_id}-preview.npy"
            np.save(out, arr)
            print(f"{out}: shape={arr.shape} dtype={arr.dtype} "
                  f"(first {cut} frames folded in)")
        elif args.action == "status":
            print(json.dumps(client.status(args.job_id), indent=2))
        elif args.action == "wait":
            print(json.dumps(client.wait(args.job_id,
                                         timeout=args.timeout), indent=2))
        elif args.action == "result":
            arr = client.result(args.job_id, dataset=args.dataset)
            out = args.out or f"{args.job_id}.npy"
            np.save(out, arr)
            print(f"{out}: shape={arr.shape} dtype={arr.dtype}")
        elif args.action == "cancel":
            print(json.dumps(client.cancel(args.job_id), indent=2))
        elif args.action == "trace":
            if args.otlp:
                print(json.dumps(client.trace(args.job_id, otlp=True),
                                 indent=2))
            elif args.json:
                print(json.dumps(client.trace(args.job_id), indent=2))
            else:
                print(client.trace(args.job_id, text=True), end="")
        elif args.action == "slo":
            snap = client.slo()
            print(_slo_text(snap) if args.format == "text"
                  else json.dumps(snap, indent=2))
        elif args.action == "events":
            _events_main(client, args)
        elif args.action == "cluster":
            doc = client.cluster()
            print(_cluster_text(doc) if args.format == "text"
                  else json.dumps(doc, indent=2))
        elif args.action == "jobs":
            print(json.dumps(client.jobs(), indent=2))
        elif args.action == "stats":
            print(json.dumps(client.stats(), indent=2))
        elif args.action == "metrics":
            print(client.metrics(), end="")
        elif args.action == "plugins":
            print(json.dumps(client.plugins(), indent=2))
    except ServiceError as e:
        raise SystemExit(f"error: {e}")


def main(argv: list[str] | None = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if argv[:1] == ["client"]:
        return _client_main(argv[1:])
    args = _build_parser().parse_args(argv)
    setup_compilation_cache()
    if args.serve is not None:
        return _serve_main(args)
    if args.workers_remote is not None:
        return _remote_demo(args)
    return _demo_main(args)


if __name__ == "__main__":
    main()
