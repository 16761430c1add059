"""Serve controller actor: deployment state machine + autoscaler + health.

Capability parity with the reference controller
(reference: ``python/ray/serve/_private/controller.py:86`` — app/deployment
state reconciliation; ``deployment_state.py`` — replica lifecycle;
``autoscaling_state.py:262`` — metrics-driven target computation), rebuilt
as a single sync actor whose reconcile loop runs on a daemon thread and
whose RPC methods run on the actor's thread pool (this runtime's actors are
thread-concurrent, not asyncio-concurrent).
"""
from __future__ import annotations

import math
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

from .._private import events as _events
from .autoscaler import (PLAIN_GROUP, Autoscaler, DesiredStateJournal,
                         replica_actor_name)
from .config import AutoscalingConfig, DeploymentConfig


class ServeController:
    RECONCILE_INTERVAL_S = 0.1

    def __init__(self):
        # Lock order: _reconcile_lock (outer, serializes every scaling /
        # teardown mutation across the RPC threads and the loop thread)
        # then _lock (inner, guards state reads/writes).
        self._reconcile_lock = threading.RLock()
        self._lock = threading.RLock()
        self._apps: Dict[str, dict] = {}
        self._http_info: Optional[dict] = None
        self._replica_counter = 0
        # SLO-driven autoscaling + crash-safe desired state (ISSUE 17):
        # the autoscaler turns health-pass signals into bounded scaling
        # decisions; the journal write-aheads every target change and
        # replica intent to the cluster KV so a SIGKILLed controller's
        # successor resumes reconciliation idempotently (_maybe_recover).
        self._autoscaler = Autoscaler()
        self._journal = DesiredStateJournal()
        self._recovered = False
        # dname -> (tpot_p95_or_None, fetched_at): head-merged latency,
        # refreshed at most ~1/s for deployments with a TPOT SLO.
        self._tpot_cache: Dict[str, tuple] = {}
        # Test hook (mirrors engine.inject_fault): named reconcile
        # points that hard-exit the controller process, for crash-safe
        # reconciliation chaos tests.
        self._crash_points: set = set()
        # Proxy fleet (reference: proxy_state_manager — one proxy per
        # node): node_id -> {"handle", "info"}. Populated once
        # ensure_proxies() records the bind options.
        self._proxies: Dict[str, dict] = {}
        self._proxy_opts: Optional[dict] = None
        # node_id -> {"shed_total", "expired_total"} pulled from each
        # proxy on the health pass (request-lifecycle visibility).
        self._proxy_stats: Dict[str, dict] = {}
        self._stop = threading.Event()
        self._loop_thread = threading.Thread(
            target=self._reconcile_loop, daemon=True, name="rt-serve-ctrl")
        self._loop_thread.start()

    # -------------------------------------------------------------- deploy
    def deploy_app(self, spec: dict) -> dict:
        """Deploy (or redeploy) an application.

        ``spec`` = {name, route_prefix, ingress,
        deployments: [{name, payload, config: DeploymentConfig}]}.
        Blocks until every deployment has its initial target of healthy
        replicas (reference: ``serve.run(..., _blocking=True)``).
        """
        name = spec["name"]
        with self._reconcile_lock:
            # Adopt any journaled fleet FIRST: a redeploy racing a
            # controller restart must see the adopted replicas or it
            # would start a duplicate set (double scale-up).
            self._maybe_recover()
            with self._lock:
                app = self._apps.setdefault(
                    name, {"name": name, "route_prefix": None,
                           "ingress": None, "deployments": {}})
                app["route_prefix"] = spec.get("route_prefix")
                app["ingress"] = spec["ingress"]
                app["stream"] = bool(spec.get("stream"))
                wanted = {d["name"] for d in spec["deployments"]}
                removed = [app["deployments"].pop(dname)
                           for dname in list(app["deployments"])
                           if dname not in wanted]
            for dstate in removed:
                self._teardown_deployment(dstate)
            # _apply_deployment only mutates state under _lock; the
            # blocking replica RPCs it schedules (teardown of replaced
            # deployments, reconfigure fan-out) run here, outside _lock,
            # so status()/get_replicas() stay responsive during redeploys.
            deferred = []
            with self._lock:
                for dspec in spec["deployments"]:
                    deferred.extend(self._apply_deployment(app, dspec))
            for action in deferred:
                action()
            # Journal the app spec + desired targets BEFORE the first
            # reconcile actuates them: a controller killed mid-rollout
            # must find the full desired state, not a torso.
            self._journal_app(name)
            self._reconcile_once()
        # The reconcile above waited for every replica it started to
        # finish constructing — weights, engine pools and the whole
        # compiled-program set included — or to die trying: readiness
        # is an event the runtime reports, not a deadline guessed from
        # how long a small model takes. So the app is ready now, or a
        # replica failed and the caller of serve.run() gets that
        # replica's own error (a compiler message would otherwise stay
        # in a worker log on a machine that may be thrown away). A pass
        # that did neither (its health sweep raised first) is repeated,
        # a bounded number of times.
        for attempt in range(3):
            if attempt:
                self._reconcile_once()
            if self._app_ready(name):
                return self.status()
            with self._lock:
                app = self._apps.get(name) or {"deployments": {}}
                errors = {dname: d["start_error"]
                          for dname, d in app["deployments"].items()
                          if d.get("start_error")}
            if errors:
                break
        raise RuntimeError(
            f"app {name!r} did not become ready; replica start errors: "
            f"{errors or 'none recorded'}")

    def _apply_deployment(self, app: dict, dspec: dict) -> list:
        """Mutate deployment state; returns deferred blocking actions for
        the caller to run outside the state lock."""
        dname = dspec["name"]
        cfg: DeploymentConfig = dspec["config"]
        cur = app["deployments"].get(dname)
        deferred = []
        if cur is not None and cur["payload"] == dspec["payload"]:
            if cur["config"] != cfg:
                cur["config"] = cfg
                cur["target"] = cfg.initial_target()
                cur["role_targets"] = self._role_targets(cfg)
                replicas = list(cur["replicas"].values())
                deferred.append(lambda: [
                    self._call_quietly(r["handle"].reconfigure,
                                       cfg.user_config) for r in replicas])
                cur["version"] += 1
            return deferred
        if cur is not None:
            deferred.append(lambda c=cur: self._teardown_deployment(c))
        app["deployments"][dname] = {
            "app": app["name"],
            "name": dname,
            "payload": dspec["payload"],
            "config": cfg,
            "target": cfg.initial_target(),
            # Heterogeneous role groups within ONE deployment
            # (ISSUE 14): an ``engine: roles: {prefill: n, decode: m}``
            # block reconciles per role — each replica is started with
            # its role stamped into its engine config, and roles scale
            # and drain independently.
            "role_targets": self._role_targets(cfg),
            "version": 0,
            "replicas": {},
            "scale": {"desired": None, "since": 0.0, "last_metric": 0.0},
            "last_health": 0.0,
        }
        return deferred

    @staticmethod
    def _role_targets(cfg: DeploymentConfig) -> Optional[Dict[str, int]]:
        eng = cfg.engine_config or {}
        roles = eng.get("roles")
        if eng.get("role") == "prefill":
            # The bare spelling pins EVERY replica's engine to one
            # role, but only a ``roles:`` group teaches the controller
            # and router to two-hop — an all-prefill deployment would
            # hard-fail every plain stream (engine.submit refuses on a
            # prefill-role engine). Same trap the roles-block guard
            # below rejects, so reject this spelling too.
            raise ValueError(
                "engine role 'prefill' cannot be applied "
                "deployment-wide (no replica could decode); use "
                "roles: {prefill: n, decode: m} for disaggregation")
        if roles and eng.get("role"):
            raise ValueError(
                "engine block carries both 'role' and 'roles'; pick "
                "one (a roles: group stamps each replica's role)")
        if not roles:
            return None
        out = {}
        for role, n in roles.items():
            if role not in ("prefill", "decode", "both"):
                raise ValueError(f"unknown engine role {role!r} in "
                                 f"roles block {roles}")
            if int(n) < 0:
                raise ValueError(f"negative target for role {role!r}")
            out[role] = int(n)
        if out.get("prefill", 0) > 0 and \
                out.get("decode", 0) + out.get("both", 0) == 0:
            # A prefill-only fleet can never finish a stream: the
            # router filters all traffic to decode-capable replicas
            # the moment a prefill role exists, so every request would
            # queue until its deadline. Reject at deploy time.
            raise ValueError(
                f"roles block {roles} has prefill replicas but no "
                f"decode-capable ones (decode/both); streams could "
                f"never complete")
        return out

    def _teardown_deployment(self, dstate: dict):
        with self._reconcile_lock:
            with self._lock:
                dstate["deleted"] = True
                victims = list(dstate["replicas"].values())
                dstate["replicas"] = {}
                dstate["version"] += 1
            self._drain_and_kill(
                victims, dstate["config"].graceful_shutdown_timeout_s,
                dstate["name"], app_name=dstate.get("app"))

    def _drain_and_kill(self, victims: list, timeout_s: float,
                        deployment: str, app_name: Optional[str] = None):
        """Graceful drain before any teardown (reconfigure, scale-down,
        health replacement, app delete), then the kill: each replica
        stops admitting (retryable pushback → routers re-pick), running
        engine lanes finish, stragglers fail retryably so clients
        resume elsewhere. Drains are fired in PARALLEL and gathered
        under ONE shared budget — N stalled victims cost the same wall
        time as one, so a wide scale-down cannot wedge the control
        loop. Drain count/duration are observed HERE — the controller
        outlives the replica, so the observation always ships.

        With ``app_name`` the victims are journaled CONDEMNED before
        the first drain RPC (crash-safe scale-down, ISSUE 17): a
        controller killed anywhere in this method leaves its successor
        a durable instruction to re-drain and kill them — named
        replicas are detached actors and would otherwise outlive
        everyone as orphans."""
        from .. import api as rt
        from .._private.metrics import serve_metrics

        if not victims:
            return
        if app_name is not None:
            try:
                self._journal_intents(
                    app_name, deployment,
                    {r["rid"]: ("condemned", r.get("role"))
                     for r in victims if r.get("rid")})
            except Exception:  # noqa: BLE001 - journal lag; drain anyway
                traceback.print_exc()
            self._maybe_crash("drain_condemned")
        _events.emit("controller.drain", phase="begin",
                     deployment=deployment,
                     replicas=[r.get("rid", "") for r in victims],
                     timeout_s=timeout_s)
        t0 = time.time()
        refs = []
        for r in victims:
            try:
                refs.append(r["handle"].drain.remote(timeout_s))
            except Exception:  # noqa: BLE001 - already-dead actor
                pass
        if refs:
            try:
                rt.wait(refs, num_returns=len(refs),
                        timeout=timeout_s + 2)
            except Exception:  # noqa: BLE001 - degrade to the kills
                pass
        self._maybe_crash("drain_pre_kill")
        sm = serve_metrics()
        labels = {"deployment": deployment}
        dt = time.time() - t0
        for r in victims:
            sm["replica_drains"].inc(labels=labels)
            sm["drain_duration"].observe(dt, labels=labels)
            try:
                rt.kill(r["handle"])
            except Exception:  # noqa: BLE001
                pass
        _events.emit("controller.drain", phase="end",
                     deployment=deployment,
                     replicas=[r.get("rid", "") for r in victims],
                     elapsed_s=round(dt, 3))
        if app_name is not None:
            try:
                self._journal_intents(
                    app_name, deployment,
                    {r["rid"]: None for r in victims if r.get("rid")})
            except Exception:  # noqa: BLE001 - stale CONDEMNED entries
                # are re-killed (idempotent) by the next recovery sweep
                traceback.print_exc()

    # ------------------------------------------------------------ queries
    def get_replicas(self, app_name: str, deployment_name: str,
                     pending: int = 0, router_id: str = ""
                     ) -> Optional[dict]:
        # Routers piggyback their blocked-admission queue depth on the
        # membership refresh (ISSUE 17): with zero replicas there is no
        # replica to report load, so this is the scale-from-zero demand
        # signal. Reports of 0 matter too — they clear the demand.
        if router_id:
            self._autoscaler.note_pending(app_name, deployment_name,
                                          router_id, pending, time.time())
        with self._lock:
            app = self._apps.get(app_name)
            if app is None:
                return None
            d = app["deployments"].get(deployment_name)
            if d is None:
                return None
            return {"version": d["version"],
                    "max_ongoing_requests": d["config"].max_ongoing_requests,
                    # Router-side pending bound before shedding with
                    # BackPressureError (request-lifecycle layer).
                    "max_queued_requests": getattr(
                        d["config"], "max_queued_requests", 64),
                    "replicas": {rid: r["handle"]
                                 for rid, r in d["replicas"].items()},
                    # rid -> node_id, for locality-preferring routing
                    # (reference: pow_2_scheduler prefer_local_node).
                    "replica_nodes": {rid: r.get("node_id")
                                      for rid, r in d["replicas"].items()},
                    # Disaggregation role groups (ISSUE 14): routers
                    # two-hop generation across prefill/decode groups.
                    "replica_roles": {rid: r.get("role") or "both"
                                      for rid, r in
                                      d["replicas"].items()},
                    # Replicas mid-graceful-drain: routers must keep
                    # them OUT of the pick set until this list clears
                    # (a drain pushback mark must not self-expire).
                    "draining": [rid for rid, r in d["replicas"].items()
                                 if r.get("draining")]}

    def get_routes(self) -> Dict[str, dict]:
        with self._lock:
            out = {}
            for name, app in self._apps.items():
                if app["route_prefix"]:
                    out[app["route_prefix"]] = {
                        "app": name, "ingress": app["ingress"],
                        "stream": bool(app.get("stream"))}
            return out

    def get_ingress(self, app_name: str) -> Optional[str]:
        with self._lock:
            app = self._apps.get(app_name)
            return app["ingress"] if app else None

    def status(self) -> dict:
        with self._lock:
            apps = {}
            for name, app in self._apps.items():
                deps = {}
                for dname, d in app["deployments"].items():
                    n_healthy = len(d["replicas"])
                    role_targets = d.get("role_targets")
                    if role_targets:
                        # Role-split deployments (ISSUE 14): the fleet
                        # target is the SUM over role groups, and the
                        # deployment is healthy only when EVERY group
                        # meets its own target — one surviving prefill
                        # replica serves nothing if both decode
                        # replicas are gone.
                        role_counts: Dict[str, int] = {}
                        for r in d["replicas"].values():
                            rr = r.get("role") or "both"
                            role_counts[rr] = role_counts.get(rr, 0) + 1
                        target = sum(role_targets.values())
                        healthy = all(role_counts.get(role, 0) >= n
                                      for role, n in role_targets.items())
                    else:
                        target = d["target"]
                        healthy = n_healthy >= target
                    deps[dname] = {
                        "status": "HEALTHY" if healthy else "UPDATING",
                        "replicas": n_healthy,
                        "target": target,
                        # Shed/expired/overload visibility (collected on
                        # the health pass; see _health_check).
                        "lifecycle": dict(d.get("lifecycle") or
                                          {"expired": 0, "overloaded": 0,
                                           "total": 0, "drains": 0}),
                    }
                    # Paged decode-engine visibility (pages free/used,
                    # prefix hits, COW forks), same health-pass ride.
                    if d.get("engine"):
                        deps[dname]["engine"] = dict(d["engine"])
                    # Autoscaler diagnosability (ISSUE 17): per-group
                    # signal freshness next to the engine block — a
                    # held decision (stale_signal / missing_signal) is
                    # explicable from status() alone — plus the last
                    # decision per group.
                    if d["config"].autoscaling_config is not None \
                            or role_targets:
                        groups: Dict[str, list] = {}
                        if role_targets:
                            for role in role_targets:
                                groups[role] = [
                                    rid for rid, r in
                                    d["replicas"].items()
                                    if (r.get("role") or "both") == role]
                        else:
                            groups[PLAIN_GROUP] = list(d["replicas"])
                        deps[dname]["signal_age_s"] = \
                            self._autoscaler.signal_ages(
                                name, dname, groups, time.time())
                        last = self._autoscaler.last_decisions(name,
                                                               dname)
                        if last:
                            deps[dname]["autoscale"] = last
                apps[name] = {"route_prefix": app["route_prefix"],
                              "ingress": app["ingress"],
                              "deployments": deps}
            proxy_stats = dict(self._proxy_stats)
            lifecycle = {
                "proxy_shed_total": sum(s.get("shed_total", 0)
                                        for s in proxy_stats.values()),
                "proxy_expired_total": sum(s.get("expired_total", 0)
                                           for s in proxy_stats.values()),
            }
            out = {"applications": apps, "http": self._http_info,
                   "lifecycle": lifecycle}
        self._attach_latency(out)
        return out

    def _attach_latency(self, status: dict):
        """Per-deployment latency block (p50/p95/p99 from the
        cluster-merged histogram buckets): e2e, TTFT, and TPOT as
        observed by every caller-side router in the cluster, plus the
        queue-wait split. Best-effort — a head hiccup leaves status
        without the block rather than failing it. Runs OUTSIDE the state
        lock (it is an RPC to the head)."""
        try:
            from ..core.worker import CoreWorker

            merged = CoreWorker.current().head_call("metrics_merged")
        except Exception:  # noqa: BLE001 - status stays useful without it
            return
        from .._private.metrics import histogram_summary

        for app in status["applications"].values():
            for dname, d in app["deployments"].items():
                block = {}
                for key, metric in (
                        ("e2e", "serve_request_e2e_seconds"),
                        ("ttft", "serve_ttft_seconds"),
                        ("tpot", "serve_tpot_seconds")):
                    s = histogram_summary(merged, metric,
                                          {"deployment": dname})
                    if s is not None:
                        block[key] = s
                for where in ("router", "replica"):
                    s = histogram_summary(
                        merged, "serve_queue_wait_seconds",
                        {"deployment": dname, "where": where})
                    if s is not None:
                        block[f"queue_wait_{where}"] = s
                if block:
                    d["latency"] = block

    def set_http_info(self, info: dict):
        # rtlint RT101 (real finding): every other writer/reader of
        # _http_info holds _lock; an unguarded RPC write here could be
        # lost under a concurrent _reconcile_proxies publish.
        with self._lock:
            self._http_info = info

    def get_http_info(self) -> Optional[dict]:
        return self._http_info

    def delete_app(self, name: str) -> bool:
        with self._lock:
            app = self._apps.pop(name, None)
        if app is None:
            return False
        for d in app["deployments"].values():
            self._teardown_deployment(d)
        # Journal LAST: the condemn/kill path above is crash-safe on
        # its own, and clearing first would leave a killed controller's
        # successor no instruction to finish the teardown.
        try:
            self._journal.del_app(name)
        except Exception:  # noqa: BLE001 - stale journal; recovery
            # re-drains the (already dead) fleet idempotently
            traceback.print_exc()
        self._autoscaler.forget(name)
        return True

    def shutdown_serve(self):
        from .. import api as rt

        self._stop.set()
        for name in list(self._apps):
            self.delete_app(name)
        # Under _reconcile_lock: an in-flight _reconcile_proxies could
        # otherwise finish creating a proxy AFTER this teardown and
        # leak it (still holding the SERVE_PROXY name) past shutdown.
        with self._reconcile_lock:
            with self._lock:
                proxies, self._proxies = dict(self._proxies), {}
                self._proxy_opts = None
            for p in proxies.values():
                try:
                    rt.kill(p["handle"])
                except Exception:  # noqa: BLE001
                    pass
        return True

    def ping(self) -> bool:
        return True

    # --------------------------------------------------------- reconcile
    def _app_ready(self, name: str) -> bool:
        with self._lock:
            app = self._apps.get(name)
            if app is None:
                return False
            return all(len(d["replicas"]) >= d["target"]
                       for d in app["deployments"].values())

    def _reconcile_loop(self):
        while not self._stop.wait(self.RECONCILE_INTERVAL_S):
            try:
                self._reconcile_once()
            except Exception:  # noqa: BLE001 - keep the loop alive
                traceback.print_exc()

    def _reconcile_once(self):
        with self._reconcile_lock:
            try:
                self._maybe_recover()
            except Exception:  # noqa: BLE001 - retried next tick
                traceback.print_exc()
            try:
                self._reconcile_proxies()
            except Exception:  # noqa: BLE001
                traceback.print_exc()
            with self._lock:
                work = [(app_name, dname, d)
                        for app_name, app in self._apps.items()
                        for dname, d in app["deployments"].items()]
            for app_name, dname, d in work:
                if d.get("deleted"):
                    continue
                try:
                    self._health_check(d)
                    self._autoscale(d)
                    self._scale_to_target(app_name, dname, d)
                except Exception:  # noqa: BLE001
                    traceback.print_exc()

    #: Whole-pass budget for gathering health probes. A replica that
    #: accepts the RPC but never replies used to wedge the entire pass
    #: (serial per-probe waits); now the pass waits AT MOST this long in
    #: aggregate and any probe still unanswered counts as FAILED.
    _HEALTH_PROBE_TIMEOUT_S = 5.0

    def _health_check(self, d: dict):
        from .. import api as rt

        period = d["config"].health_check_period_s
        ac = d["config"].autoscaling_config
        if ac is not None:
            # The health pass doubles as the autoscaler's signal
            # scrape: cap its cadence at the configured metrics
            # interval so decision freshness tracks the config, not
            # the (coarser) health period.
            period = min(period, max(ac.metrics_interval_s, 0.05))
        if time.time() - d["last_health"] < period:
            return
        d["last_health"] = time.time()
        with self._lock:
            probes = [(rid, r["handle"].check_health.remote(),
                       r["handle"].get_metrics.remote())
                      for rid, r in d["replicas"].items()]
        if not probes:
            return
        # Bounded gather: one shared deadline for the whole pass, not a
        # fresh window per replica — N wedged replicas cost the same as
        # one. Probes not ready at the deadline are failed probes.
        deadline = time.monotonic() + self._HEALTH_PROBE_TIMEOUT_S
        try:
            ready, _ = rt.wait([ref for _rid, ref, _m in probes],
                               num_returns=len(probes),
                               timeout=self._HEALTH_PROBE_TIMEOUT_S)
            ready = set(ready)
        except Exception:  # noqa: BLE001 - degrade to bounded gets
            ready = {ref for _rid, ref, _m in probes}
        dead = []
        # Live-replica lifecycle totals (expired / overloaded / served),
        # piggybacked on the health pass and surfaced via status().
        life = {"expired": 0, "overloaded": 0, "total": 0, "drains": 0}
        # Engine page/prefix totals (decode engines only),
        # summed across replicas, same piggyback.
        engine: dict = {}
        for rid, ref, mref in probes:
            try:
                if ref not in ready:
                    raise TimeoutError(
                        f"health probe to {rid} unanswered after "
                        f"{self._HEALTH_PROBE_TIMEOUT_S}s")
                ok = rt.get(ref,
                            timeout=max(deadline - time.monotonic(), 0.1))
                if not ok:
                    dead.append(rid)
                    continue
            except Exception:  # noqa: BLE001 - died, hung, or timed out
                dead.append(rid)
                continue
            # Metrics scrape is best-effort: only a failed HEALTH probe
            # may kill a replica — a momentarily stalled get_metrics
            # (e.g. user code holding the GIL through a long compile)
            # must not take down a healthy replica.
            try:
                m = rt.get(mref,
                           timeout=max(deadline - time.monotonic(), 0.1))
                # Autoscaler signal feed (ISSUE 17): a replica whose
                # scrape fails simply records nothing this round, and
                # the decision loop degrades to a hold for its group.
                self._autoscaler.record(d["app"], d["name"], rid, m,
                                        time.time())
                life["expired"] += int(m.get("expired", 0))
                life["overloaded"] += int(m.get("overloaded", 0))
                life["total"] += int(m.get("total", 0))
                life["drains"] += int(m.get("drains", 0))
                for est in m.get("engines") or []:
                    for key in ("pages_free", "pages_used",
                                "prefix_hits", "cow_copies",
                                "admissions_deferred", "lane_parks",
                                "preempted", "prefix_tokens_reused",
                                "active_slots", "slots", "queue_depth",
                                "resumed", "driver_restarts",
                                "attn_kernel_dispatches"):
                        if key in est:
                            engine[key] = engine.get(key, 0) + est[key]
                    engine["paged"] = engine.get("paged", False) \
                        or bool(est.get("paged"))
                    # Kernel/quantization identity (ISSUE 16): config,
                    # not counters — pass through, don't sum. Replicas
                    # of one deployment share the knobs, so last wins.
                    for key in ("attn_kernel", "kv_dtype",
                                "kv_bytes_per_token", "tp"):
                        if key in est:
                            engine[key] = est[key]
                    sp = est.get("spec")
                    if sp:
                        agg = engine.setdefault(
                            "spec", {"drafter": sp.get("drafter", "")})
                        for key in ("rounds", "proposed", "accepted",
                                    "lanes", "fallback_rounds"):
                            agg[key] = agg.get(key, 0) + int(
                                sp.get(key, 0))
                    ev = est.get("events")
                    if ev and ev.get("enabled"):
                        # Flight-recorder health (ISSUE 19): summed
                        # emit/drop totals plus the WORST ring fill —
                        # a deployment-wide view of whether the rings
                        # are keeping up, from serve.status() alone.
                        agg = engine.setdefault("events", {})
                        for key in ("emitted", "dropped_total",
                                    "truncated"):
                            agg[key] = agg.get(key, 0) + int(
                                ev.get(key, 0))
                        agg["ring_fill"] = max(
                            agg.get("ring_fill", 0.0),
                            float(ev.get("ring_fill", 0.0)))
                    ho = est.get("handoff")
                    if ho:
                        # Disaggregation visibility (ISSUE 14): summed
                        # across roles, so exported ~= imported +
                        # fallbacks + outstanding + reclaimed is
                        # checkable from serve.status() alone.
                        agg = engine.setdefault("handoff", {})
                        for key in ("exported", "imported",
                                    "import_fallbacks", "ship_bytes",
                                    "leases_outstanding",
                                    "leases_claimed",
                                    "leases_reclaimed"):
                            agg[key] = agg.get(key, 0) + int(
                                ho.get(key, 0))
            except Exception:  # noqa: BLE001 - totals dip this round
                pass
        # Prune autoscaler signals for replicas the controller no
        # longer lists (dead, drained, or scaled away) — a ghost entry
        # would keep feeding a stale load reading into the decision.
        with self._lock:
            live = set(d["replicas"])
        self._autoscaler.prune(d["app"], d["name"], live, time.time())
        d["lifecycle"] = life
        if engine:
            sp = engine.get("spec")
            if sp:
                # Deployment-wide acceptance: replica counters summed
                # above, the rates derived once here.
                sp["acceptance_rate"] = round(
                    sp["accepted"] / max(sp["proposed"], 1), 4)
                sp["accepted_per_forward"] = round(
                    (sp["accepted"] + sp["lanes"])
                    / max(sp["lanes"], 1), 3)
            d["engine"] = engine
        if dead:
            for rid in dead:
                _events.emit("controller.replica_dead", replica=rid,
                             deployment=d["name"], cause="health_probe")
            with self._lock:
                victims = []
                for rid in dead:
                    r = d["replicas"].pop(rid, None)
                    if r is not None:
                        victims.append(r)
                d["version"] += 1
            # Membership already dropped (routers stop picking on the
            # next refresh); give a wedged-but-alive replica the chance
            # to fail its in-flight lanes RETRYABLY before the kill —
            # hard-killing first would turn every stream it still holds
            # into an actor-death error race. A genuinely dead actor
            # fails the drain RPC instantly. The budget is CAPPED at the
            # probe timeout here — the victim already failed a health
            # probe, and a wedged replica that swallows the drain RPC
            # must not stall the control loop for the full graceful
            # window per victim.
            self._drain_and_kill(
                victims, min(d["config"].graceful_shutdown_timeout_s,
                             self._HEALTH_PROBE_TIMEOUT_S), d["name"],
                app_name=d["app"])

    def _autoscale(self, d: dict):
        """SLO-driven autoscale tick (ISSUE 17): per role group, turn
        the health-pass signal book into a bounded target change. The
        decision logic lives in ``autoscaler.decide`` (hysteresis,
        cooldowns, step caps, stale-signal holds, scale-to-zero,
        cold-start grace); this method only snapshots the groups,
        applies the returned targets, and journals them — actuation
        stays with ``_scale_to_target``, whose scale-down path drains
        before every kill."""
        ac: Optional[AutoscalingConfig] = d["config"].autoscaling_config
        if ac is None:
            return
        role_targets = d.get("role_targets")
        if role_targets and not ac.roles:
            # Without per-role autoscaling overrides the roles block IS
            # the target per role (declarative disaggregation, ISSUE
            # 14); a fleet-wide ongoing signal cannot apportion
            # replicas between compute-bound prefill and
            # bandwidth-bound decode.
            return
        now = time.time()
        if now - d["scale"]["last_metric"] < ac.metrics_interval_s:
            return
        d["scale"]["last_metric"] = now
        app_name, dname = d["app"], d["name"]
        with self._lock:
            if role_targets:
                groups = {
                    role: {"cur": tgt,
                           "rids": [rid for rid, r in
                                    d["replicas"].items()
                                    if (r.get("role") or "both") == role]}
                    for role, tgt in role_targets.items()}
            else:
                groups = {PLAIN_GROUP: {"cur": d["target"],
                                        "rids": list(d["replicas"])}}
        decisions = self._autoscaler.tick(
            app_name, dname, ac, groups, now,
            tpot_p95=self._tpot_p95(dname, ac, now))
        changed = False
        with self._lock:
            for group, dec in decisions.items():
                if dec.direction == "hold":
                    continue
                if group == PLAIN_GROUP:
                    if d["target"] != dec.target:
                        d["target"] = dec.target
                        changed = True
                elif d.get("role_targets") is not None and \
                        d["role_targets"].get(group) != dec.target:
                    d["role_targets"][group] = dec.target
                    changed = True
        if changed:
            try:
                self._journal_desired(app_name)
            except Exception:  # noqa: BLE001 - journal lag: a crash
                # now resumes from the previous targets, which the
                # next tick's decision re-derives from live signals
                traceback.print_exc()

    def _tpot_p95(self, dname: str, ac: AutoscalingConfig,
                  now: float) -> Optional[float]:
        """Cluster-merged TPOT p95 for one deployment, cached ~1 s.
        Only fetched when a TPOT SLO is configured; any head hiccup
        degrades the SLO overlay to absent rather than failing the
        tick."""
        wants = ac.tpot_slo_s is not None or any(
            (o or {}).get("tpot_slo_s") is not None
            for o in (ac.roles or {}).values())
        if not wants:
            return None
        cached = self._tpot_cache.get(dname)
        if cached and now - cached[1] < max(ac.metrics_interval_s, 1.0):
            return cached[0]
        val = None
        try:
            from ..core.worker import CoreWorker

            from .._private.metrics import histogram_summary

            merged = CoreWorker.current().head_call("metrics_merged")
            s = histogram_summary(merged, "serve_tpot_seconds",
                                  {"deployment": dname})
            val = s.get("p95_s") if s else None
        except Exception:  # noqa: BLE001 - SLO overlay absent this tick
            pass
        self._tpot_cache[dname] = (val, now)
        return val

    def _scale_to_target(self, app_name: str, dname: str, d: dict):
        with self._lock:
            role_targets = d.get("role_targets")
        self._reap_stray_roles(dname, d, role_targets)
        if role_targets:
            # Heterogeneous role groups (ISSUE 14): each role
            # reconciles against ITS target — prefill and decode scale
            # and drain independently inside one deployment.
            for role, target in role_targets.items():
                self._scale_role(app_name, dname, d, role, target)
            return
        self._scale_role(app_name, dname, d, None, None)

    def _reap_stray_roles(self, dname: str, d: dict,
                          role_targets: Optional[Dict[str, int]]):
        """Drain replicas whose stamped role matches no current role
        group (a redeploy added, removed, or reshaped the ``roles:``
        block): without this, a plain replica would sit outside every
        per-role count forever, and a role-stamped leftover under a
        plain target would keep rejecting the traffic routed to it —
        its engine role cannot be changed live."""
        with self._lock:
            valid = set(role_targets) if role_targets else {None}
            stray = {rid: r for rid, r in d["replicas"].items()
                     if r.get("role") not in valid}
            if not stray:
                return
            for rid in stray:
                d["replicas"].pop(rid, None)
            d["version"] += 1
            cfg = d["config"]
        self._drain_and_kill(list(stray.values()),
                             cfg.graceful_shutdown_timeout_s, dname,
                             app_name=d["app"])

    def _scale_role(self, app_name: str, dname: str, d: dict,
                    role: Optional[str], target: Optional[int]):
        from .. import api as rt

        with self._lock:
            members = {rid: r for rid, r in d["replicas"].items()
                       if role is None or (r.get("role") or "both")
                       == role}
            have = len(members)
            if target is None:
                target = d["target"]
            cfg = d["config"]
        if have < target:
            new = []
            for _ in range(target - have):
                try:
                    new.append(self._start_replica(app_name, dname, d,
                                                   role=role))
                except Exception as e:  # noqa: BLE001 - journal/create
                    # failure: retried next tick (intent, if written,
                    # is swept by recovery)
                    traceback.print_exc()
                    d["start_error"] = f"{type(e).__name__}: {e}"
            ok = []
            for rid, handle in new:
                try:
                    # No deadline: the wait ends when the constructor
                    # returns or the actor dies (the head reports
                    # either), however long a cold compile takes.
                    handle._wait_ready()
                    try:
                        node_id = rt.get(handle.get_node_id.remote(),
                                         timeout=10)
                    except Exception:  # noqa: BLE001 - routing hint only
                        node_id = None
                    self._maybe_crash("scale_up_created")
                    ok.append((rid, handle, node_id))
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    d["start_error"] = f"{type(e).__name__}: {e}"
                    # Never-ready replica: kill it and clear its
                    # intent, or the named (detached) actor would
                    # linger as an orphan no journal entry describes.
                    try:
                        rt.kill(handle)
                    except Exception:  # noqa: BLE001
                        pass
                    try:
                        self._journal_intents(app_name, dname,
                                              {rid: None})
                    except Exception:  # noqa: BLE001 - swept later
                        pass
            if ok:
                d.pop("start_error", None)
                with self._lock:
                    for rid, handle, node_id in ok:
                        d["replicas"][rid] = {"handle": handle,
                                              "rid": rid,
                                              "node_id": node_id,
                                              "role": role,
                                              "created": time.time()}
                    d["version"] += 1
                # Confirm AFTER membership: a crash in between leaves
                # STARTING + a live actor, which recovery adopts.
                try:
                    self._journal_intents(
                        app_name, dname,
                        {rid: ("live", role) for rid, _h, _n in ok})
                except Exception:  # noqa: BLE001 - stays STARTING;
                    # recovery adopts it the same way
                    traceback.print_exc()
        elif have > target:
            with self._lock:
                victims = sorted(members.items(),
                                 key=lambda kv: kv[1]["created"],
                                 reverse=True)[:have - target]
                for rid, _ in victims:
                    d["replicas"].pop(rid, None)
                d["version"] += 1
            self._drain_and_kill([r for _rid, r in victims],
                                 cfg.graceful_shutdown_timeout_s, dname,
                                 app_name=app_name)

    def drain_role(self, app_name: str, deployment_name: str, role: str,
                   remove: bool = True,
                   timeout_s: Optional[float] = None) -> list:
        """Drain ONE role group of a disaggregated deployment
        independently of the others (ISSUE 14): its replicas are marked
        draining (``get_replicas`` lists them, so routers pin them out
        of the pick set — no self-expiring mark), their engines drain
        gracefully, and with ``remove=True`` they are torn down and the
        role's target zeroed so the reconcile loop does not respawn
        them. Returns the drained replica ids."""
        with self._reconcile_lock:
            with self._lock:
                app = self._apps.get(app_name)
                d = (app or {"deployments": {}})["deployments"] \
                    .get(deployment_name)
                if d is None:
                    return []
                victims = {rid: r for rid, r in d["replicas"].items()
                           if (r.get("role") or "both") == role}
                for r in victims.values():
                    r["draining"] = True
                d["version"] += 1
                cfg = d["config"]
            budget = cfg.graceful_shutdown_timeout_s \
                if timeout_s is None else float(timeout_s)
            if not victims:
                return []
            if not remove:
                # Mark-and-drain only: replicas stay listed (as
                # draining) so routers hold their marks; the caller
                # removes them later (or redeploys).
                from .. import api as rt

                refs = []
                for r in victims.values():
                    try:
                        refs.append(r["handle"].drain.remote(budget))
                    except Exception:  # noqa: BLE001 - already dead
                        pass
                if refs:
                    try:
                        rt.wait(refs, num_returns=len(refs),
                                timeout=budget + 2)
                    except Exception:  # noqa: BLE001 - best-effort
                        pass
                return sorted(victims)
            with self._lock:
                for rid in victims:
                    d["replicas"].pop(rid, None)
                if d.get("role_targets"):
                    d["role_targets"][role] = 0
                d["version"] += 1
            try:
                self._journal_desired(app_name)
            except Exception:  # noqa: BLE001 - recovery re-zeroes via
                # the condemned intents below
                traceback.print_exc()
            self._drain_and_kill(list(victims.values()), budget,
                                 deployment_name, app_name=app_name)
            return sorted(victims)

    # -------------------------- crash-safe desired state (ISSUE 17)
    def _journal_app(self, name: str):
        """Journal one app's full spec (payloads + configs) and its
        desired targets. Raises on journal failure — deploy_app is the
        only caller and a deploy that cannot be made durable should
        fail loudly, not silently lose crash safety."""
        with self._lock:
            app = self._apps.get(name)
            if app is None:
                return
            blob = {"name": name,
                    "route_prefix": app["route_prefix"],
                    "ingress": app["ingress"],
                    "stream": bool(app.get("stream")),
                    "deployments": [
                        {"name": d["name"], "payload": d["payload"],
                         "config": d["config"]}
                        for d in app["deployments"].values()]}
        self._journal.put_app(name, blob)
        self._journal_desired(name)

    def _journal_desired(self, app_name: str):
        with self._lock:
            app = self._apps.get(app_name)
            if app is None:
                return
            desired = {dname: {"target": d["target"],
                               "role_targets": d.get("role_targets")}
                       for dname, d in app["deployments"].items()}
        self._journal.put_desired(app_name, desired)

    def _journal_intents(self, app_name: str, dname: str,
                         updates: Dict[str, Any]):
        """Apply ``{rid: None | (state, role)}`` to the app's replica
        intent document (one read-modify-write; every caller holds
        ``_reconcile_lock``, which serializes them)."""
        intents = self._journal.get_replicas(app_name)
        ents = intents.setdefault(dname, {})
        for rid, up in updates.items():
            if up is None:
                ents.pop(rid, None)
            else:
                state, role = up
                ents[rid] = {"role": role, "state": state,
                             "t": time.time()}
        if not ents:
            intents.pop(dname, None)
        self._journal.put_replicas(app_name, intents)

    def _maybe_recover(self):
        """Resume reconciliation from the journal after a controller
        restart (idempotent, runs once per controller life).

        For every journaled app: rebuild deployment state from the
        spec + desired-target documents, then reconcile the replica
        intents against reality — a LIVE/STARTING entry whose named
        actor answers is ADOPTED (counted toward its group's target,
        so no double scale-up), an entry with no live actor is dropped
        (the create never landed, or the replica died with nobody
        watching), and CONDEMNED entries are re-drained and killed
        (the predecessor was mid-scale-down; clients resume on the
        survivors). Orphans are impossible as long as intents are
        written ahead of creates — every live replica has an entry,
        and every entry is either adopted or torn down here."""
        with self._lock:
            if self._recovered:
                return
            self._recovered = True
        try:
            names = self._journal.list_apps()
        except Exception:  # noqa: BLE001 - head unreachable: flip the
            # gate back so the next tick retries recovery
            with self._lock:
                self._recovered = False
            return
        for name in names:
            with self._lock:
                if name in self._apps:
                    continue
            try:
                self._recover_app(name)
            except Exception:  # noqa: BLE001 - one app's bad journal
                # must not block the others (or the loop)
                traceback.print_exc()

    def _recover_app(self, name: str):
        from .. import api as rt

        blob = self._journal.get_app(name)
        if blob is None:
            return
        desired = self._journal.get_desired(name)
        intents = self._journal.get_replicas(name)
        app = {"name": name, "route_prefix": blob.get("route_prefix"),
               "ingress": blob.get("ingress"),
               "stream": bool(blob.get("stream")), "deployments": {}}
        for dspec in blob.get("deployments", []):
            dname = dspec["name"]
            cfg: DeploymentConfig = dspec["config"]
            want = desired.get(dname) or {}
            app["deployments"][dname] = {
                "app": name, "name": dname,
                "payload": dspec["payload"], "config": cfg,
                "target": int(want.get("target",
                                       cfg.initial_target())),
                "role_targets": want.get("role_targets",
                                         self._role_targets(cfg)),
                "version": 0, "replicas": {},
                "scale": {"desired": None, "since": 0.0,
                          "last_metric": 0.0},
                "last_health": 0.0,
            }
        survivors: Dict[str, dict] = {}
        condemned: Dict[str, list] = {}
        for dname, ents in intents.items():
            d = app["deployments"].get(dname)
            for rid, ent in ents.items():
                try:
                    n = int(rid.rsplit("#", 1)[1])
                except (IndexError, ValueError):
                    n = 0
                # Past the journaled ids, or a fresh create would
                # collide with an adopted name.
                self._replica_counter = max(self._replica_counter, n)
                try:
                    handle = rt.get_actor(replica_actor_name(name, rid),
                                          timeout=2)
                except Exception:  # noqa: BLE001 - no such actor
                    handle = None
                if handle is None:
                    continue       # entry dropped: nothing to adopt
                if d is None or ent.get("state") == "condemned":
                    # Keep the entry CONDEMNED until the kill below
                    # completes — a crash mid-recovery must leave the
                    # re-drain instruction in place.
                    survivors.setdefault(dname, {})[rid] = {
                        "role": ent.get("role"), "state": "condemned",
                        "t": time.time()}
                    condemned.setdefault(dname, []).append(
                        {"handle": handle, "rid": rid,
                         "role": ent.get("role")})
                    continue
                try:
                    node_id = rt.get(handle.get_node_id.remote(),
                                     timeout=5)
                except Exception:  # noqa: BLE001 - routing hint only
                    node_id = None
                d["replicas"][rid] = {"handle": handle, "rid": rid,
                                      "node_id": node_id,
                                      "role": ent.get("role"),
                                      "created": time.time()}
                survivors.setdefault(dname, {})[rid] = {
                    "role": ent.get("role"), "state": "live",
                    "t": time.time()}
        with self._lock:
            self._apps[name] = app
        self._journal.put_replicas(name, survivors)
        for dname, victims in condemned.items():
            d = app["deployments"].get(dname)
            budget = d["config"].graceful_shutdown_timeout_s if d \
                else 5.0
            self._drain_and_kill(victims, budget, dname, app_name=name)

    def inject_crash(self, point: str) -> bool:
        """Chaos-test hook (mirrors ``engine.inject_fault``): hard-exit
        the controller process (``os._exit(44)``) the next time the
        reconcile path passes ``point``. Points: ``scale_up_intent``
        (intent journaled, actor not yet created), ``scale_up_created``
        (actor live, membership/journal not yet confirmed),
        ``drain_condemned`` (victims condemned, drain not yet sent),
        ``drain_pre_kill`` (drained, not yet killed)."""
        self._crash_points.add(point)
        return True

    def _maybe_crash(self, point: str):
        if point in self._crash_points:
            import os

            os._exit(44)

    def _start_replica(self, app_name: str, dname: str, d: dict,
                       role: Optional[str] = None):
        from .. import api as rt
        from ._replica import Replica

        cfg: DeploymentConfig = d["config"]
        self._replica_counter += 1
        rid = f"{dname}#{self._replica_counter}"
        # WRITE-AHEAD (ISSUE 17): the intent reaches the journal BEFORE
        # the create RPC, so every replica that can possibly exist has
        # an entry a restarted controller reconciles against — adopt if
        # it came up, sweep if it never did. A failed journal write
        # aborts the create (the safe side: no actor without an entry).
        self._journal_intents(app_name, dname, {rid: ("starting", role)})
        self._maybe_crash("scale_up_intent")
        opts = dict(cfg.ray_actor_options)
        opts.setdefault("num_cpus", 1)
        # Replicas spread across nodes by default so one node's death
        # never takes a whole deployment down (reference:
        # deployment_scheduler.py spread policy).
        opts.setdefault("scheduling_strategy", "SPREAD")
        # Named => DETACHED in this runtime: the replica survives a
        # SIGKILLed controller (streams keep flowing) and the successor
        # re-attaches by name instead of starting a duplicate.
        opts["name"] = replica_actor_name(app_name, rid)
        actor_cls = rt.remote(Replica).options(
            max_concurrency=cfg.max_ongoing_requests + 4, **opts)
        # Role stamping (ISSUE 14): the replica sees its OWN role in
        # the engine block; the deployment-level ``roles:`` group
        # sizing is controller state and never reaches the engine.
        engine_config = dict(getattr(cfg, "engine_config", None) or {})
        engine_config.pop("roles", None)
        if role:
            engine_config["role"] = role
        # The replica enforces max_ongoing_requests itself: client-side
        # admission undercounts when several routers share one replica,
        # so the server gate (typed ReplicaOverloadedError pushback) is
        # the authoritative one.
        handle = actor_cls.remote(app_name, dname, rid, d["payload"],
                                  cfg.user_config,
                                  cfg.max_ongoing_requests,
                                  engine_config or None)
        return rid, handle

    # ------------------------------------------------------------- proxies
    def ensure_proxies(self, http_options: dict) -> Optional[dict]:
        """Record the proxy bind options and start one proxy per alive
        node (reference: ``proxy.py:1116`` — a proxy on every node, any
        of them serves external traffic). Returns the primary proxy's
        bind info. The reconcile loop keeps the fleet in sync as nodes
        join and leave."""
        with self._reconcile_lock:
            self._proxy_opts = dict(http_options)
            self._reconcile_proxies()
            return self._http_info

    def get_proxies(self) -> Dict[str, dict]:
        """node_id -> {"name", "info"} for every live proxy."""
        with self._lock:
            return {nid: {"name": p["name"], "info": p["info"]}
                    for nid, p in self._proxies.items()}

    _PROXY_HEALTH_PERIOD_S = 5.0

    def _reconcile_proxies(self):
        if self._proxy_opts is None:
            return
        from .. import api as rt
        from ..util.state import list_nodes
        from ._proxy import ProxyActor

        alive = {n["node_id"]: n for n in list_nodes()
                 if n.get("state") == "ALIVE"}
        with self._lock:
            have = set(self._proxies)
        # Reap proxies whose node died (the actor died with it).
        for nid in have - set(alive):
            with self._lock:
                p = self._proxies.pop(nid, None)
            if p is not None:
                try:
                    rt.kill(p["handle"])
                except Exception:  # noqa: BLE001 - already dead
                    pass
        # A proxy can also die on a LIVE node (crash/OOM): probe each
        # one periodically and drop dead entries so the create loop
        # below resurrects them — replicas get health checks, proxies
        # must too (reference: proxy_state_manager health states).
        now = time.time()
        if now - getattr(self, "_proxies_checked_at", 0.0) \
                >= self._PROXY_HEALTH_PERIOD_S:
            self._proxies_checked_at = now
            with self._lock:
                probes = [(nid, p["handle"], p["name"])
                          for nid, p in self._proxies.items()]
            for nid, handle, name in probes:
                try:
                    rt.get(handle.get_port.remote(), timeout=5)
                except Exception:  # noqa: BLE001 - proxy dead
                    with self._lock:
                        self._proxies.pop(nid, None)
                        self._proxy_stats.pop(nid, None)
                    try:
                        rt.kill(handle)
                    except Exception:  # noqa: BLE001
                        pass
                    continue
                # Piggyback shed/expired totals for status(); tolerate
                # adopted proxies predating the RPC.
                try:
                    stats = rt.get(handle.get_lifecycle_stats.remote(),
                                   timeout=5)
                    with self._lock:
                        self._proxy_stats[nid] = stats
                except Exception:  # noqa: BLE001 - older proxy
                    pass
        opts = self._proxy_opts
        primary_missing = not any(p["name"] == "SERVE_PROXY"
                                  for p in self._proxies.values())
        for nid, node in alive.items():
            if nid in self._proxies:
                continue
            # The first proxy keeps the legacy cluster-wide name (and
            # the configured port); secondaries are per-node actors on
            # an ephemeral port — co-hosted test nodes must not fight
            # over one port, and real deployments address each node's
            # proxy by its own host anyway.
            name = "SERVE_PROXY" if primary_missing \
                else f"SERVE_PROXY:{nid[:12]}"
            port = opts.get("port", 0) if primary_missing else 0
            try:
                handle = rt.remote(ProxyActor).options(
                    name=name, max_concurrency=8, num_cpus=0,
                    scheduling_strategy=rt.NodeAffinitySchedulingStrategy(
                        nid, soft=True)).remote()
                info = rt.get(handle.start.remote(
                    opts.get("host", "127.0.0.1"), port,
                    opts.get("request_timeout_s", 60.0)), timeout=30)
            except Exception as e:  # noqa: BLE001 - node raced away; retry
                # A prior fleet's proxy may still hold the name (this
                # controller restarted or lost state): adopt the live
                # actor instead of colliding with the identical create
                # on every reconcile tick and never publishing
                # _http_info (ADVICE.md low).
                adopted = None
                if "already taken" in str(e):
                    adopted = self._adopt_proxy(name, opts, port)
                if adopted is None:
                    traceback.print_exc()
                    continue
                handle, info = adopted
            with self._lock:
                self._proxies[nid] = {"handle": handle, "name": name,
                                      "info": info}
                if primary_missing:
                    self._http_info = dict(info)
                    primary_missing = False

    def _adopt_proxy(self, name: str, opts: dict, bind_port: int):
        """Adopt a live proxy actor that already holds ``name``:
        ``get_port`` is idempotent (None until started), and ``start``
        is only issued when the actor never bound — re-starting a bound
        proxy would spawn a second server thread. ``bind_port`` is the
        caller's computed port for this slot (configured port for the
        primary, 0 for secondaries — adopting a secondary must not bind
        the primary's port). Returns (handle, info) or None if the
        actor is gone/unresponsive (the name then frees up and the next
        tick's create succeeds)."""
        from .. import api as rt

        try:
            handle = rt.get_actor(name, timeout=5)
            port = rt.get(handle.get_port.remote(), timeout=5)
            if port is None:
                info = rt.get(handle.start.remote(
                    opts.get("host", "127.0.0.1"), bind_port,
                    opts.get("request_timeout_s", 60.0)), timeout=30)
            else:
                info = {"host": opts.get("host", "127.0.0.1"),
                        "port": port}
            return handle, info
        except Exception:  # noqa: BLE001 - stale name or dead actor
            return None

    @staticmethod
    def _call_quietly(method, *args):
        from .. import api as rt

        try:
            rt.get(method.remote(*args), timeout=10)
        except Exception:  # noqa: BLE001
            pass
