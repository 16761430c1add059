"""Dashboard SPA + its API surface end-to-end (reference: the core
views of dashboard/client/src served over the head's HTTP endpoint)."""
import json
import time
import urllib.request


def _get(url, timeout=15):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def _get_json(url, timeout=15):
    return json.loads(_get(url, timeout).decode())


def test_dashboard_spa_and_all_apis_multinode():
    """Every endpoint the SPA consumes works against a live 2-node
    cluster: state kinds, per-node agent stats, worker log tail, jobs +
    job logs, timeline, metrics, and the page itself."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    if ray_tpu.is_initialized():
        # an earlier file of this worker left conftest.rt_cluster's
        # session up for reuse (files run back to back under loadfile)
        ray_tpu.shutdown()
    c = Cluster(head_resources={"CPU": 2})
    rt = c.connect()
    try:
        c.add_node(num_cpus=2)
        c.wait_for_nodes(2)
        url = c.head.dashboard.url

        # --- the SPA itself: full page with every view's container
        page = _get(url + "/").decode()
        for needle in ("ray_tpu", "cluster", "jobs", "actors", "workers",
                       "events", "/api/state", "/api/node", "/api/jobs",
                       "/api/job_logs", "/api/logs"):
            assert needle in page, f"SPA missing {needle!r}"

        # --- live state behind the cluster view
        @rt.remote
        class Pinger:
            def ping(self):
                return "ok"

        a = Pinger.options(name="dash_actor").remote()
        assert rt.get(a.ping.remote()) == "ok"

        summary = _get_json(url + "/api/state?kind=summary")
        assert summary["nodes"] == 2
        nodes = _get_json(url + "/api/state?kind=nodes")
        assert len(nodes) == 2
        actors = _get_json(url + "/api/state?kind=actors")
        assert any(x["name"] == "dash_actor" for x in actors)
        workers = _get_json(url + "/api/state?kind=workers")
        assert workers, "no workers listed"

        # --- per-node agent stats proxied through the head
        remote_node = next(n for n in nodes if not n["is_head"])
        stats = _get_json(url + "/api/node?node_id="
                          + remote_node["node_id"])
        assert "cpu_percent" in json.dumps(stats)

        # --- worker log tail through the head
        wid = workers[0]["worker_id"]
        log = _get_json(url + "/api/logs?worker_id=" + wid)
        assert "data" in log

        # --- jobs view + job logs
        from ray_tpu.job_submission import JobSubmissionClient

        client = JobSubmissionClient(c.address)
        job_id = client.submit_job(
            entrypoint="python -c \"print('dash job ran')\"")
        deadline = time.time() + 60
        while time.time() < deadline:
            jobs = _get_json(url + "/api/jobs")
            rec = next((j for j in jobs if j["job_id"] == job_id), None)
            if rec is not None and rec["status"] in ("SUCCEEDED", "FAILED"):
                break
            time.sleep(0.3)
        assert rec is not None and rec["status"] == "SUCCEEDED", rec
        logs = _get_json(url + "/api/job_logs?job_id=" + job_id)
        assert "dash job ran" in logs["logs"]

        # --- timeline + metrics
        timeline = _get_json(url + "/api/timeline")
        assert isinstance(timeline, list)
        metrics = _get(url + "/metrics").decode()
        assert "ray_tpu" in metrics or "#" in metrics
    finally:
        c.shutdown()
