"""Cross-process serving: the HTTP front end over the JobQueue.

End-to-end per the PR acceptance criteria: an in-process server on an
ephemeral port, PipelineClient submissions at mixed priorities polled to
completion with results bit-identical to a serial PluginRunner; 429 on
admission rejection; 400 with the validation error for malformed specs;
compile-cache hits visible in GET /stats on identical resubmission."""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from repro.core import ChunkedFileTransport, PluginRunner, ShardedTransport
from repro.service import (CompileCache, PipelineClient, PipelineService,
                           ServiceError, to_spec)
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
    three disjoint children: the device wait, the copy to the host and
    the send; the copy's bytes reach ``transfer.d2h_bytes``."""
    svc, client = service
    jid = client.submit(_chain(seed=3))
    assert client.wait(jid, timeout=300)["state"] == "done"
    before = svc.metrics.counter("transfer.d2h_bytes").value
    vol = client.result(jid)
    spans = client.trace(jid)["spans"]
    (fetch,) = [s for s in spans if s["name"] == "result.fetch"]
    children = sorted((s for s in spans
                       if s.get("parent_id") == fetch["span_id"]),
                      key=lambda s: s["start"])
    assert [s["name"] for s in children] == [
        "result.device_wait", "transfer.d2h", "result.send"]
    for a, b in zip(children, children[1:]):
        assert a["end"] <= b["start"]
    assert fetch["start"] <= children[0]["start"]
    assert children[-1]["end"] <= fetch["end"]
    assert children[1]["attrs"]["bytes"] == vol.nbytes
    assert svc.metrics.counter("transfer.d2h_bytes").value - before \
        == vol.nbytes
    assert "transfer_d2h_bytes" in client.metrics()
    # the run itself: job.run is the parent of the plugin spans, and the
    # raw scan's copy to the device was counted
    (run,) = [s for s in spans if s["name"] == "job.run"]
    plugin = [s for s in spans if s["name"].startswith("plugin.")]
    assert plugin and all(s["parent_id"] == run["span_id"] for s in plugin)
    assert svc.metrics.counter("transfer.h2d_bytes").value > 0
