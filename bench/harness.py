"""One run of one benchmark cell.

Everything a cell is made of is found by name: its configuration in the
file ``BENCHMARK.json`` gives it, its traffic mix in
``traffic/<traffic>.json`` (data, paced by the loop it names:
``loops/<loop>.py``, see ``generator.py``), and each metric's reader in
``metrics/<metric>.py`` (a function ``read(run)`` that returns a number,
or None when the run holds nothing for it to read).  The run:

1. serves the configuration's process list with ``PipelineService``
   (scheduler mode, ``ShardedTransport`` on a mesh of the cell's chips,
   the configuration's service settings) on localhost and warms it up
   with the mix's warm-up requests;
2. drives the window with the mix's loop, with the profiler on when
   ``trace`` is set;
3. reads the device's peak memory, stops the service and frees it;
4. checks the volumes that the window's requests delivered against the
   plain reference (``reference.py``) at a sample of image rows drawn
   from the seed;
5. reduces the records and the trace to the cell's metrics.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys

import numpy as np

import jax

import generator
import metric_lib
import reduce_trace
import reference

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Clock times (``generator.clock``) at which JAX finished compiling
    or loading a program, from JAX's own monitoring events."""

    def __init__(self):
        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.times.append(generator.clock() - duration)

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


@dataclasses.dataclass
class Run:
    """What a metric's reader reads."""
    cell: dict
    config: dict
    traffic: dict
    setup_s: float
    t0: float                              # window, on generator.clock
    t1: float
    requests: list                         # generator.Request, window only
    compiles_in_window: int
    peaks: dict
    device: dict
    events: list | None = None             # reduce_trace.Event, --trace 1
    trace_window: tuple | None = None      # (start_ns, end_ns) in the trace

    @property
    def done(self) -> list:
        return [r for r in self.requests if r.error is None]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _read_json(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as fh:
        return json.load(fh)


def cell_parts(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """(cell, config, traffic) of a workload named in ``bench``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(configs[cell["config"]]["file"])
    traffic = _read_json(os.path.join("bench", "traffic",
                                      cell["traffic"] + ".json"))
    return cell, config, traffic


def metrics_of(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports: end-to-end ones
    without a trace, per-layer ones with."""
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def reader(name: str):
    """``read`` of ``metrics/<name>.py``."""
    return metric_lib.load("metrics", name).read


def device_info(devices, chips: int, platform: str) -> dict:
    """Platform, kind and count of JAX's devices; raises unless they are
    ``platform`` devices and at least ``chips`` of them."""
    found = devices[0].platform
    if found != platform:
        raise RuntimeError(f"no {platform} devices: JAX found {found}")
    if len(devices) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX found "
                           f"{len(devices)}")
    return {"platform": found, "kind": devices[0].device_kind,
            "count": len(devices)}


def peaks_for(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; peaks.json "
                       f"has {sorted(table)}")
    return table[kind]


def serve(config: dict, devices):
    """(service, client): the configuration's process lists served on
    localhost over a mesh of ``devices``.  ``config["service"]`` holds
    ``donate`` (the transport's) and ``PipelineService``'s settings."""
    from jax.sharding import Mesh

    from repro.core import ShardedTransport
    from repro.service import CompileCache, PipelineClient, PipelineService
    settings = dict(config["service"])
    donate = settings.pop("donate")
    mesh = Mesh(np.asarray(devices), ("data",))
    cache = CompileCache()
    service = PipelineService(
        transport_factory=lambda job: ShardedTransport(
            mesh, donate=donate, compile_cache=cache),
        compile_cache=cache, **settings)
    host, port = service.serve(host="127.0.0.1", port=0)
    return service, PipelineClient(f"http://{host}:{port}")


def _memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def out_size(config: dict) -> int:
    """The side of the reconstructed images."""
    fbp = reference.chain_params(config["process_list"])["fbp_recon"]
    return fbp.get("out_size") or metric_lib.loader(config)["n_det"]


def check_rows(config: dict, seed: int) -> np.ndarray:
    """The ``check.image_rows`` image rows that a run with ``seed``
    compares, sorted."""
    n = out_size(config)
    rng = np.random.default_rng([seed % 2**64, 2])
    return np.sort(rng.choice(n, size=min(config["check"]["image_rows"], n),
                              replace=False))


def _chain_key(spec: dict) -> str:
    """A request's process list without its loader seed: the requests
    that one reference batch can share."""
    spec = json.loads(json.dumps(spec))
    for e in spec["plugins"]:
        if e["plugin"] == "synthetic_tomo_loader":
            e["params"].pop("seed", None)
    return json.dumps(spec, sort_keys=True)


def check(config: dict, requests: list, seed: int, log) -> dict:
    """Compare every volume the window delivered with the reference at
    :func:`check_rows`.  Returns each number compared with its limit."""
    chk = config["check"]
    loader = metric_lib.loader(config)
    n_rows, side = loader["n_rows"], out_size(config)
    rows = check_rows(config, seed)
    worst = {"max_err": 0.0, "rms_err": 0.0}
    done = [r for r in requests if r.error is None
            and r.volume is not None
            and r.volume.shape == (n_rows, side, side)
            and np.all(np.isfinite(r.volume[:, rows, :]))]
    groups: dict[str, list] = {}
    for r in done:
        groups.setdefault(_chain_key(r.spec), []).append(r)
    per_batch = max(1, chk["slices_per_batch"] // n_rows)
    for group in groups.values():
        spec = group[0].spec
        for i in range(0, len(group), per_batch):
            batch = group[i:i + per_batch]
            sinos, mu = [], None
            for r in batch:
                s, mu = reference.filtered_sinograms(
                    spec, config["phantom"], r.seed)
                sinos.append(s)
            want = reference.volume_rows(
                spec, jax.numpy.concatenate(sinos), mu, rows)
            del sinos
            for j, r in enumerate(batch):
                errs = reference.compare(r.volume[:, rows, :],
                                         want[j * n_rows:(j + 1) * n_rows])
                for k in worst:
                    worst[k] = max(worst[k], errs[k])
    failed = len(requests) - len(done)
    log(f"check: {len(done)} volumes at {len(rows)} image rows; "
        f"{failed} failed, missing or malformed")
    numbers = {k: {"value": worst[k], "limit": chk["limits"][k]}
               for k in worst}
    numbers["failed"] = {"value": failed, "limit": 0}
    return numbers


def passes(numbers: dict) -> bool:
    """Every number at or under its limit.  A window always sends a
    request, so a run with nothing compared has ``failed`` > 0."""
    return all(v["value"] <= v["limit"] for v in numbers.values())


def drive_cell(bench: dict, workload: str, *, seed: int, t_start: float,
               seconds: float | None = None, count: int | None = None,
               trace: bool = False, platform: str = "tpu",
               config: dict | None = None, traffic: dict | None = None,
               log=None) -> Run:
    """Serve ``workload``, warm it up and drive its window: ``seconds``
    of its traffic, or ``count`` requests of it.  Returns the run with
    the delivered requests, the service stopped and freed.

    ``t_start`` is the process's start on :func:`generator.clock`.
    ``platform`` is what JAX's devices must be; ``config`` and
    ``traffic`` replace the cell's (the tests shrink them)."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell, cell_config, cell_traffic = cell_parts(bench, workload)
    config = config or cell_config
    traffic = traffic or cell_traffic
    all_devices = jax.devices()
    device = device_info(all_devices, cell["chips"], platform)
    devices = all_devices[:cell["chips"]]
    peaks = peaks_for(device["kind"]) if platform == "tpu" else {}
    compiles = CompileLog()

    t_serve = generator.clock()
    service, client = serve(config, devices)
    t_warm = generator.clock()
    try:
        warm, _, _ = generator.drive(
            client, generator.Source(config, traffic, seed, 0, "warmup"),
            traffic, count=traffic["warmup_requests"])
        for r in warm:
            if r.error:
                raise RuntimeError(f"warm-up request failed: {r.error}")
        del warm
        setup_s = generator.clock() - t_start
        log(f"setup: {setup_s:.3f} s: {t_serve - t_start:.3f} s to JAX's "
            f"devices, {t_warm - t_serve:.3f} s to a served chip, "
            f"{generator.clock() - t_warm:.3f} s of warm-up "
            f"({len(compiles.times)} programs compiled or loaded)")
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(reduce_trace.WINDOW_SPAN):
                requests, t0, t1 = generator.drive(
                    client, generator.Source(config, traffic, seed, 1,
                                             "req"),
                    traffic, seconds=seconds, count=count)
        finally:
            if trace:
                jax.profiler.stop_trace()
        device["memory_peak_bytes"] = _memory_peak(devices)
    finally:
        service.stop()
    del service, client
    gc.collect()

    run = Run(cell=cell, config=config, traffic=traffic, setup_s=setup_s,
              t0=t0, t1=t1, requests=requests,
              compiles_in_window=compiles.between(t0, t1), peaks=peaks,
              device=device)
    lat = sorted(r.latency_s for r in run.done)
    log(f"window: {t1 - t0:.3f} s, {len(run.done)} of {len(requests)} "
        f"requests done, {run.compiles_in_window} compiles inside; "
        f"latency samples {len(lat)}, "
        f"min {lat[0] if lat else None}, max {lat[-1] if lat else None}")
    if trace:
        run.events = reduce_trace.load_events(
            reduce_trace.find_xspace(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        run.trace_window = reduce_trace.window(run.events)
    return run


def run_cell(bench: dict, workload: str, *, seed: int, seconds: float,
             trace: bool, t_start: float, platform: str = "tpu",
             config: dict | None = None, traffic: dict | None = None,
             log=None) -> dict:
    """One run of ``workload`` (see :func:`drive_cell`); returns the
    result line's object."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    run = drive_cell(bench, workload, seed=seed, t_start=t_start,
                     seconds=seconds, trace=trace, platform=platform,
                     config=config, traffic=traffic, log=log)
    device, result = run.device, {}
    if trace:
        lo, hi = run.trace_window
        device["busy_s"] = reduce_trace.busy_ns(run.events, lo, hi) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = {
            "device_ops": reduce_trace.top_ops(run.events, lo, hi),
            "idle_gaps": reduce_trace.idle_by_host_span(run.events, lo, hi)}
    values = {}
    for m in metrics_of(bench, workload, trace):
        v = reader(m["name"])(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    t = generator.clock()
    numbers = check(run.config, run.requests, seed, log)
    log(f"reference: {generator.clock() - t:.3f} s")
    for k, v in numbers.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    return {"correct": passes(numbers), "attempted": len(run.requests),
            "failed": len(run.requests) - len(run.done), "metrics": values,
            "device": device, **result, "check": numbers}
