"""Cross-process serving: the HTTP front end over the JobQueue.

End-to-end per the PR acceptance criteria: an in-process server on an
ephemeral port, PipelineClient submissions at mixed priorities polled to
completion with results bit-identical to a serial PluginRunner; 429 on
admission rejection; 400 with the validation error for malformed specs;
compile-cache hits visible in GET /stats on identical resubmission."""
import io
import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from repro.core import (ChunkedFileTransport, DataSet, PluginRunner,
                        ShardedTransport)
from repro.service import (CompileCache, PipelineClient, PipelineService,
                           ServiceError, to_spec)
from repro.service import server as server_module
from repro.tomo import standard_chain

N = dict(n_det=20, n_angles=20, n_rows=1)


def _chain(seed=0, **over):
    return standard_chain(**{**N, **over}, seed=seed)


@pytest.fixture
def service():
    """A served PipelineService on an ephemeral port (sharded transport,
    shared compile cache) + a client for it."""
    cache = CompileCache()
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    svc = PipelineService(
        n_workers=2, compile_cache=cache,
        transport_factory=lambda job: ShardedTransport(
            mesh, donate=False, compile_cache=cache))
    host, port = svc.serve(port=0)
    client = PipelineClient(f"http://{host}:{port}", timeout=30.0)
    try:
        yield svc, client
    finally:
        svc.stop()


# ------------------------------------------------------------- end-to-end
def test_end_to_end_submit_poll_result(service):
    svc, client = service
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    seeds_prios = [(0, 5), (1, 0), (2, 2)]
    ids = [client.submit(_chain(seed=s), priority=p,
                         metadata={"seed": s})
           for s, p in seeds_prios]
    for (seed, prio), jid in zip(seeds_prios, ids):
        snap = client.wait(jid, timeout=300)
        assert snap["state"] == "done", snap
        assert snap["priority"] == prio
        assert snap["metadata"]["seed"] == seed
        assert snap["plugin_index"] == snap["n_plugins"] > 0
        got = client.result(jid)
        # serial reference on the same transport type: bit-identical
        ref = PluginRunner(_chain(seed=seed),
                           ShardedTransport(mesh, donate=False)).run()
        want = np.asarray(ref["recon"].materialise())
        np.testing.assert_array_equal(got, want)

    # identical resubmission: zero new compiles, hits visible in /stats
    before = client.stats()["compile_cache"]
    jid = client.submit(_chain(seed=9))
    assert client.wait(jid, timeout=300)["state"] == "done"
    after = client.stats()["compile_cache"]
    assert after["misses"] == before["misses"]
    assert after["hits"] > before["hits"]
    assert client.stats()["jobs_done"] == 4


def test_result_streams_from_chunked_files(tmp_path):
    svc = PipelineService(
        n_workers=1,
        transport_factory=lambda job: ChunkedFileTransport(
            str(tmp_path / job.job_id)))
    host, port = svc.serve(port=0)
    client = PipelineClient(f"http://{host}:{port}")
    try:
        jid = client.submit(_chain(seed=3))
        assert client.wait(jid, timeout=300)["state"] == "done"
        got = client.result(jid, dataset="recon")
        ref = PluginRunner(_chain(seed=3)).run()
        np.testing.assert_allclose(
            got, np.asarray(ref["recon"].materialise()),
            rtol=1e-3, atol=1e-4)
    finally:
        svc.stop()


# ----------------------------------------------------------- error paths
def test_admission_rejection_is_429():
    svc = PipelineService(n_workers=1, max_pending=1)
    # scheduler workers deliberately NOT started: jobs stay pending
    host, port = svc.serve(port=0)
    svc.scheduler.shutdown()
    client = PipelineClient(f"http://{host}:{port}")
    try:
        client.submit(_chain())
        with pytest.raises(ServiceError) as ei:
            client.submit(_chain(seed=1))
        assert ei.value.status == 429
        assert "max_pending" in ei.value.message
    finally:
        svc.stop()


def test_unknown_plugin_spec_is_400(service):
    _, client = service
    with pytest.raises(ServiceError) as ei:
        client.submit({"plugins": [{"plugin": "warp_drive"}]})
    assert ei.value.status == 400
    assert "warp_drive" in ei.value.message


def test_structurally_broken_chain_is_400(service):
    _, client = service
    spec = {"plugins": [{"plugin": "synthetic_tomo_loader",
                         "params": {"n_det": 16},
                         "out_datasets": ["tomo"]}]}   # no saver
    with pytest.raises(ServiceError) as ei:
        client.submit(spec)
    assert ei.value.status == 400
    assert "saver" in ei.value.message


def test_malformed_json_body_is_400(service):
    svc, client = service
    req = urllib.request.Request(
        client.base_url + "/jobs", data=b"{not json",
        method="POST", headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=10)
    assert ei.value.code == 400
    assert "JSON" in json.loads(ei.value.read())["error"]


def test_unknown_job_is_404(service):
    _, client = service
    for call in (lambda: client.status("ghost"),
                 lambda: client.result("ghost"),
                 lambda: client.cancel("ghost")):
        with pytest.raises(ServiceError) as ei:
            call()
        assert ei.value.status == 404


def test_duplicate_active_job_id_is_409():
    svc = PipelineService(n_workers=1)
    host, port = svc.serve(port=0)
    svc.scheduler.shutdown()                 # keep the first job queued
    client = PipelineClient(f"http://{host}:{port}")
    try:
        client.submit(_chain(), job_id="twin")
        with pytest.raises(ServiceError) as ei:
            client.submit(_chain(seed=1), job_id="twin")
        assert ei.value.status == 409
    finally:
        svc.stop()


def test_result_before_done_is_409():
    svc = PipelineService(n_workers=1)
    host, port = svc.serve(port=0)
    svc.scheduler.shutdown()                 # job stays queued
    client = PipelineClient(f"http://{host}:{port}")
    try:
        jid = client.submit(_chain())
        with pytest.raises(ServiceError) as ei:
            client.result(jid)
        assert ei.value.status == 409
    finally:
        svc.stop()


def test_cancel_queued_job_via_http():
    svc = PipelineService(n_workers=1)
    host, port = svc.serve(port=0)
    svc.scheduler.shutdown()
    client = PipelineClient(f"http://{host}:{port}")
    try:
        jid = client.submit(_chain())
        out = client.cancel(jid)
        assert out["cancelled"] is True
        assert client.status(jid)["state"] == "cancelled"
        # a second cancel is consistently rejected (already terminal)
        with pytest.raises(ServiceError) as ei:
            client.cancel(jid)
        assert ei.value.status == 409
    finally:
        svc.stop()


def test_job_ids_with_url_unsafe_characters():
    """Ids containing spaces/'#'/'/' must stay addressable: the client
    percent-encodes path components and the server decodes them."""
    svc = PipelineService(n_workers=1)
    host, port = svc.serve(port=0)
    svc.scheduler.shutdown()                 # keep the job queued
    client = PipelineClient(f"http://{host}:{port}")
    try:
        jid = "scan 1/#7"
        assert client.submit(_chain(), job_id=jid) == jid
        assert client.status(jid)["job_id"] == jid
        assert client.cancel(jid)["cancelled"] is True
    finally:
        svc.stop()


def test_resumed_from_surfaces_over_http(tmp_path):
    """The docs §3 loop: a killed job's checkpoint + a resubmission
    under the same id → the snapshot reports resumed_from > 0."""
    from repro.service import CheckpointStore
    store = CheckpointStore(str(tmp_path))
    # simulate the kill: a partial run leaves a checkpoint behind
    r = PluginRunner(_chain(seed=7))
    r.prepare()
    r.step()
    store.save("scan-x", r)

    svc = PipelineService(n_workers=1, checkpoints=store)
    host, port = svc.serve(port=0)
    client = PipelineClient(f"http://{host}:{port}")
    try:
        jid = client.submit(_chain(seed=7), job_id="scan-x")
        snap = client.wait(jid, timeout=300)
        assert snap["state"] == "done", snap
        assert snap["resumed_from"] == 1
        ref = PluginRunner(_chain(seed=7)).run()
        np.testing.assert_allclose(
            client.result(jid), np.asarray(ref["recon"].materialise()),
            rtol=1e-3, atol=1e-4)
    finally:
        svc.stop()


# ------------------------------------------------------------- discovery
def test_healthz_jobs_and_plugins(service):
    svc, client = service
    assert client.health()["ok"] is True
    jid = client.submit(_chain())
    client.wait(jid, timeout=300)
    assert any(j["job_id"] == jid for j in client.jobs())
    reg = client.plugins()
    assert "fbp_recon" in reg
    assert reg["synthetic_tomo_loader"]["params"]["seed"]["data_param"]


def test_spec_submission_equals_processlist_submission(service):
    """A spec document POSTed raw behaves exactly like a ProcessList
    serialised client-side."""
    _, client = service
    spec = to_spec(_chain(seed=4))
    j1 = client.submit(spec)
    j2 = client.submit(_chain(seed=4))
    s1, s2 = (client.wait(j, timeout=300) for j in (j1, j2))
    assert s1["state"] == s2["state"] == "done"
    np.testing.assert_array_equal(client.result(j1), client.result(j2))


def test_result_fetch_is_traced_with_its_transfer(service):
    """A result fetch lands on the job's trace as ``result.fetch`` with
    two disjoint children: the device wait, then the send; the send
    holds the slabs' copies to the host (``transfer.d2h``), whose bytes
    reach ``transfer.d2h_bytes``."""
    svc, client = service
    jid = client.submit(_chain(seed=3))
    assert client.wait(jid, timeout=300)["state"] == "done"
    before = svc.metrics.counter("transfer.d2h_bytes").value
    vol = client.result(jid)
    spans = client.trace(jid)["spans"]
    (fetch,) = [s for s in spans if s["name"] == "result.fetch"]
    children = _children(spans, fetch)
    assert [s["name"] for s in children] == [
        "result.device_wait", "result.send"]
    wait, send = children
    assert fetch["start"] <= wait["start"] <= wait["end"] <= send["start"]
    assert send["end"] <= fetch["end"]
    copies = _children(spans, send)
    assert [s["name"] for s in copies] == ["transfer.d2h"] \
        * send["attrs"]["slabs"]
    assert sum(s["attrs"]["bytes"] for s in copies) == vol.nbytes
    assert svc.metrics.counter("transfer.d2h_bytes").value - before \
        == vol.nbytes
    assert "transfer_d2h_bytes" in client.metrics()
    # the run itself: job.run is the parent of the plugin spans, and the
    # raw scan's copy to the device was counted
    (run,) = [s for s in spans if s["name"] == "job.run"]
    plugin = [s for s in spans if s["name"].startswith("plugin.")]
    assert plugin and all(s["parent_id"] == run["span_id"] for s in plugin)
    assert svc.metrics.counter("transfer.h2d_bytes").value > 0


def _children(spans, parent):
    return sorted((s for s in spans
                   if s.get("parent_id") == parent["span_id"]),
                  key=lambda s: s["start"])


def _serve_probe(svc, client, array, on_device=True):
    """A finished job whose extra dataset ``probe`` holds ``array`` (on
    the device, or as a host array): its id."""
    jid = client.submit(_chain(seed=4))
    assert client.wait(jid, timeout=300)["state"] == "done"
    ds = DataSet("probe", array.shape, array.dtype,
                 tuple(f"a{i}" for i in range(array.ndim)))
    ds.backing = jax.device_put(array) if on_device else array
    svc.queue.job(jid).runner.datasets["probe"] = ds
    return jid


@pytest.mark.parametrize("shape, dtype, slab_rows, on_device", [
    ((4, 6), np.float32, None, True),         # one slab
    ((7, 3, 5), np.float32, 3, True),         # 3 + 3 + 1 rows
    ((5, 4), np.uint16, 2, True),             # 2 + 2 + 1, not float32
    ((3, 2, 2), np.complex64, 1, True),       # a slab a row
    ((7, 3, 5), np.float32, 3, False),        # a host array, no copies
])
def test_result_streams_in_slabs(service, monkeypatch, shape, dtype,
                                 slab_rows, on_device):
    """A result goes out in axis-0 slabs of ``RESULT_SLAB_BYTES``: it
    comes back equal, its body is ``np.save``'s bytes, ``result.send``
    says how many slabs it sent, and a device result's slab copies
    (``transfer.d2h``) add up to its bytes."""
    svc, client = service
    rng = np.random.default_rng(7)
    array = (rng.standard_normal(shape) * 1000).astype(dtype)
    row = array[0].nbytes
    if slab_rows is not None:
        monkeypatch.setattr(server_module, "RESULT_SLAB_BYTES",
                            slab_rows * row + row // 2)
    n_slabs = -(-shape[0] // (slab_rows or shape[0]))
    jid = _serve_probe(svc, client, array, on_device)
    got = client.result(jid, dataset="probe")
    assert got.dtype == array.dtype and got.shape == array.shape
    np.testing.assert_array_equal(got, array)
    url = f"{client.base_url}/jobs/{jid}/result?dataset=probe"
    with urllib.request.urlopen(url, timeout=30) as resp:
        body = resp.read()
    want = io.BytesIO()
    np.save(want, array)
    assert body == want.getvalue()

    spans = client.trace(jid)["spans"]
    fetch = next(s for s in spans if s["name"] == "result.fetch"
                 and s["attrs"]["dataset"] == "probe")
    (send,) = [s for s in _children(spans, fetch)
               if s["name"] == "result.send"]
    assert send["attrs"]["slabs"] == n_slabs
    copies = [s["attrs"]["bytes"] for s in _children(spans, send)
              if s["name"] == "transfer.d2h"]
    if on_device:
        assert len(copies) == n_slabs and sum(copies) == array.nbytes
        assert copies[0] == (slab_rows or shape[0]) * row
    else:
        assert copies == []


def test_result_body_cut_short_raises(service, monkeypatch):
    """A server that stops mid-body (here: the second slab fails) gives
    the client a short body, which raises ``ServiceError``; no partly
    filled array comes back."""
    svc, client = service
    array = np.arange(6 * 4, dtype=np.float32).reshape(6, 4)
    monkeypatch.setattr(server_module, "RESULT_SLAB_BYTES",
                        2 * array[0].nbytes)
    real = server_module._host_slabs

    def first_slab_only(arr, bounds):
        slabs = real(arr, bounds)
        yield next(slabs)
        raise RuntimeError("the device went away")

    monkeypatch.setattr(server_module, "_host_slabs", first_slab_only)
    jid = _serve_probe(svc, client, array)
    with pytest.raises(ServiceError, match="cut short: 32 of 96 bytes"):
        client.result(jid, dataset="probe")


_FOUR_DEVICE_SLABS = """
import jax
jax.config.update("jax_num_cpu_devices", 4)
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from repro.service import server

mesh = Mesh(np.asarray(jax.devices()), ("data",))
a = np.arange(8 * 3 * 5, dtype=np.float32).reshape(8, 3, 5)
on_mesh = jax.device_put(a, NamedSharding(mesh, PartitionSpec("data")))
server.RESULT_SLAB_BYTES = 3 * a[0].nbytes         # 3 + 3 + 2 rows
bounds = server._slab_bounds(a.shape, a.dtype)
assert bounds == [(0, 3), (3, 6), (6, 8)], bounds
slabs = list(server._host_slabs(on_mesh, bounds))
assert [s.shape[0] for s in slabs] == [3, 3, 2]
np.testing.assert_array_equal(np.concatenate(slabs), a)
"""


def test_sharded_result_streams_in_slabs():
    """A result sharded over a 4-device data mesh goes out in the same
    axis-0 slabs, ragged last slab included, and comes back whole."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(here), "src"),
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", _FOUR_DEVICE_SLABS],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
