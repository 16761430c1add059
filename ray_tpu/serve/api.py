"""Serve public API: ``@deployment``, ``bind``, ``run``, handles, lifecycle.

Capability parity with the reference's ``ray.serve.api``
(reference: ``python/ray/serve/api.py:248`` ``deployment``, ``:545`` ``run``,
``:66`` ``start``, ``:120`` ``shutdown``, ``:780`` ``status``, ``:808`` /
``:844`` handle getters; ``serve/deployment.py`` ``Deployment`` /
``Application``). The deployment graph is serialized per-deployment with
bound sub-applications replaced by handle markers, resolved back into live
``DeploymentHandle``s at replica init.
"""
from __future__ import annotations

import threading
import time
from dataclasses import replace
from typing import Any, Callable, Dict, List, Optional, Union

import cloudpickle

from .. import api as rt
from ..exceptions import RayTpuError
from .config import (DEFAULT_APP_NAME, SERVE_CONTROLLER_NAME,
                     AutoscalingConfig, DeploymentConfig, HTTPOptions,
                     gRPCOptions)
from .handle import DeploymentHandle, _HandleMarker, reset_routers

_client_lock = threading.Lock()
_client: Dict[str, Any] = {"controller": None, "proxy": None, "http": None}
#: Single-flight bootstrap gate (rtsan RS104 real finding, ISSUE 13):
#: start() used to hold _client_lock across the WHOLE control-plane
#: bootstrap — controller creation, 60 s proxy RPCs, and get_actor's
#: retry-sleep loop — so a concurrent status()/_controller()/shutdown()
#: stalled behind a full bootstrap instead of its own short timeout.
#: Now _client_lock only ever guards the state dict; the slow work runs
#: outside it, serialized by this leader Event (followers wait, then
#: re-run the now-fast idempotent body).
_boot: Dict[str, Any] = {"ev": None}


def _boot_enter() -> "threading.Event":
    """Become the bootstrap leader, waiting out any in-flight one.
    Callers MUST pair with :func:`_boot_exit` (try/finally)."""
    while True:
        with _client_lock:
            ev = _boot["ev"]
            if ev is None:
                ev = _boot["ev"] = threading.Event()
                return ev
        # Bounded: the leader's finally publishes and clears; on the
        # pathological timeout we loop and re-contend.
        ev.wait(timeout=120)


def _boot_exit(ev: "threading.Event"):
    with _client_lock:
        if _boot["ev"] is ev:
            _boot["ev"] = None
    ev.set()


class Deployment:
    """A configured-but-unbound deployment (user class/function + config)."""

    def __init__(self, func_or_class: Callable, name: str,
                 config: DeploymentConfig):
        self.func_or_class = func_or_class
        self.name = name
        self.config = config

    def options(self, *, name: Optional[str] = None,
                num_replicas: Optional[int] = None,
                max_ongoing_requests: Optional[int] = None,
                max_queued_requests: Optional[int] = None,
                autoscaling_config: Union[None, dict,
                                          AutoscalingConfig] = None,
                user_config: Any = None,
                health_check_period_s: Optional[float] = None,
                graceful_shutdown_timeout_s: Optional[float] = None,
                ray_actor_options: Optional[dict] = None,
                engine_config: Optional[dict] = None) -> "Deployment":
        cfg = self.config
        updates: Dict[str, Any] = {}
        if num_replicas is not None:
            updates["num_replicas"] = num_replicas
        if max_ongoing_requests is not None:
            updates["max_ongoing_requests"] = max_ongoing_requests
        if max_queued_requests is not None:
            updates["max_queued_requests"] = max_queued_requests
        if autoscaling_config is not None:
            if isinstance(autoscaling_config, dict):
                autoscaling_config = AutoscalingConfig(**autoscaling_config)
            updates["autoscaling_config"] = autoscaling_config
        if user_config is not None:
            updates["user_config"] = user_config
        if health_check_period_s is not None:
            updates["health_check_period_s"] = health_check_period_s
        if graceful_shutdown_timeout_s is not None:
            updates["graceful_shutdown_timeout_s"] = graceful_shutdown_timeout_s
        if ray_actor_options is not None:
            updates["ray_actor_options"] = ray_actor_options
        if engine_config is not None:
            updates["engine_config"] = dict(engine_config)
        return Deployment(self.func_or_class, name or self.name,
                          replace(cfg, **updates))

    def bind(self, *args, **kwargs) -> "Application":
        return Application(self, args, kwargs)

    def __repr__(self):
        return f"Deployment({self.name!r})"


class Application:
    """A bound deployment graph; the root is the app's ingress."""

    def __init__(self, deployment: Deployment, args: tuple, kwargs: dict):
        self.deployment = deployment
        self.args = args
        self.kwargs = kwargs


def deployment(_func_or_class: Optional[Callable] = None, *,
               name: Optional[str] = None,
               num_replicas: Union[int, str, None] = None,
               max_ongoing_requests: Optional[int] = None,
               max_queued_requests: Optional[int] = None,
               autoscaling_config: Union[None, dict,
                                         AutoscalingConfig] = None,
               user_config: Any = None,
               health_check_period_s: Optional[float] = None,
               graceful_shutdown_timeout_s: Optional[float] = None,
               ray_actor_options: Optional[dict] = None,
               engine_config: Optional[dict] = None):
    """``@serve.deployment`` decorator (reference: ``serve/api.py:248``).

    ``num_replicas="auto"`` enables autoscaling with default bounds, like the
    reference's ``handle_num_replicas_auto``.

    **Request lifecycle** (deadline → budgeted retry → shed):

    - Every request is stamped with an absolute deadline at the edge
      (HTTP proxy: ``request_timeout_s``; handles:
      ``handle.options(timeout_s=...)``, default 60 s) and carries it
      proxy → router → replica → batcher. A replica drops an
      already-expired request before invoking user code and the batcher
      drops expired entries at flush time, so no device cycles are spent
      on answers nobody is waiting for; callers see
      ``RequestDeadlineExceeded`` (HTTP ``504``). User code can read its
      remaining budget via ``serve.get_request_deadline()``.
    - ``DeploymentResponse.result()`` retries replica death with
      exponential backoff + jitter, deducting elapsed time (a retry
      never restarts the window), and spends a per-router **retry
      budget** (token bucket fed ~10% of successes plus a small
      reserve) so a dying deployment can't amplify its own load with a
      retry storm. Streaming calls transparently re-route as long as no
      item has been delivered. When the budget or attempts are
      exhausted, the ORIGINAL error raises.
    - ``max_ongoing_requests`` is enforced on the replica itself: a
      saturated replica answers with a typed overload pushback and the
      router re-picks another replica without marking it dead. Once
      every replica is saturated and ``max_queued_requests`` callers
      are already queued, submissions shed with ``BackPressureError`` —
      the HTTP proxy maps it to ``503`` with a ``Retry-After`` header
      (the client contract: back off at least that many seconds), gRPC
      to ``RESOURCE_EXHAUSTED``. Shed/expired/retry counters are
      exported via ``_private.metrics`` and ``serve.status()``.
    """

    def decorate(obj):
        cfg = DeploymentConfig()
        nr = num_replicas
        asc = autoscaling_config
        if nr == "auto":
            nr = None
            if asc is None:
                asc = AutoscalingConfig(min_replicas=1, max_replicas=10)
        if isinstance(asc, dict):
            asc = AutoscalingConfig(**asc)
        if nr is not None:
            cfg.num_replicas = int(nr)
        if max_ongoing_requests is not None:
            cfg.max_ongoing_requests = max_ongoing_requests
        if max_queued_requests is not None:
            cfg.max_queued_requests = max_queued_requests
        cfg.autoscaling_config = asc
        if user_config is not None:
            cfg.user_config = user_config
        if health_check_period_s is not None:
            cfg.health_check_period_s = health_check_period_s
        if graceful_shutdown_timeout_s is not None:
            cfg.graceful_shutdown_timeout_s = graceful_shutdown_timeout_s
        if ray_actor_options is not None:
            cfg.ray_actor_options = dict(ray_actor_options)
        if engine_config is not None:
            # Decode-engine block (paged-KV / spec-decode knobs, and
            # the ISSUE 14 disaggregation ``roles:`` group sizing) —
            # the decorator twin of the schema's ``engine:`` block.
            cfg.engine_config = dict(engine_config)
        return Deployment(obj, name or obj.__name__, cfg)

    if _func_or_class is not None and callable(_func_or_class):
        return decorate(_func_or_class)
    return decorate


# ------------------------------------------------------------------ lifecycle
def start(http_options: Union[None, dict, HTTPOptions] = None,
          proxy: bool = True,
          grpc_options: Union[None, dict, gRPCOptions] = None):
    """Start the Serve control plane: controller + optional HTTP proxy,
    plus a gRPC ingress on the same proxy actor when ``grpc_options``
    is given (reference: ``proxy.py`` HTTPProxy + gRPCProxy)."""
    if not rt.is_initialized():
        rt.init()
    if isinstance(http_options, dict):
        http_options = HTTPOptions(**http_options)
    http_options = http_options or HTTPOptions()
    if isinstance(grpc_options, dict):
        grpc_options = gRPCOptions(**grpc_options)
    # Bootstrap runs OUTSIDE _client_lock (single-flighted by the boot
    # gate): the RPCs below block for up to 60 s and get_actor retries
    # with sleeps — holding the state lock across them starved every
    # other serve entry point (rtsan RS104 real finding).
    ev = _boot_enter()
    try:
        with _client_lock:
            ctrl = _client["controller"]
        if ctrl is None:
            ctrl = _get_or_create_controller()
            with _client_lock:
                _client["controller"] = ctrl
        with _client_lock:
            need_proxy = proxy and _client["proxy"] is None
        if need_proxy:
            # The CONTROLLER owns the proxy fleet — one per alive node
            # (reference: proxy_state_manager / proxy.py:1116) — and
            # keeps it reconciled as nodes join/leave. ensure_proxies is
            # get-or-create: an already-running fleet (a previous driver
            # or CLI invocation) is adopted, with its recorded bind info.
            info = dict(rt.get(
                ctrl.ensure_proxies.remote({
                    "host": http_options.host,
                    "port": http_options.port,
                    "request_timeout_s": http_options.request_timeout_s,
                }), timeout=60) or {})
            pr = rt.get_actor("SERVE_PROXY", timeout=10)
            with _client_lock:
                _client["proxy"] = pr
                _client["http"] = info
        with _client_lock:
            pr = _client["proxy"]
            http = _client["http"]
        if grpc_options is not None and pr is not None \
                and "grpc_port" not in (http or {}):
            # Bind the gRPC ingress on the running proxy (whether it was
            # just created or already existed) rather than silently
            # dropping the request.
            info = dict(http or {})
            info.update(rt.get(pr.start_grpc.remote(
                grpc_options.host, grpc_options.port), timeout=30))
            with _client_lock:
                _client["http"] = http = info
        if http is not None:
            rt.get(ctrl.set_http_info.remote(http), timeout=10)
        if pr is not None:
            from ..util import tracing

            # Mirror the driver's tracing state (both directions) so
            # per-request server spans record exactly when the driver
            # traces; picked up on every serve.start()/serve.run().
            try:
                rt.get(pr.set_tracing.remote(
                    tracing.enabled()), timeout=10)
            except Exception:  # noqa: BLE001 - adopted older proxy
                pass
    finally:
        _boot_exit(ev)
    return ctrl


def _get_or_create_controller():
    from ._controller import ServeController

    try:
        return rt.get_actor(SERVE_CONTROLLER_NAME, timeout=0.5)
    except Exception:  # noqa: BLE001 - not created yet
        pass
    try:
        ctrl = rt.remote(ServeController).options(
            name=SERVE_CONTROLLER_NAME, max_concurrency=16).remote()
        ctrl._wait_ready(timeout=30)
        return ctrl
    except Exception:  # noqa: BLE001 - lost a creation race
        return rt.get_actor(SERVE_CONTROLLER_NAME, timeout=10)


def run(app: Application, *, name: str = DEFAULT_APP_NAME,
        route_prefix: Optional[str] = "/", blocking: bool = False,
        _proxy: bool = True) -> DeploymentHandle:
    """Deploy an application and return a handle to its ingress
    (reference: ``serve/api.py:545``)."""
    if not isinstance(app, Application):
        raise TypeError("serve.run() takes an Application built with "
                        "`Deployment.bind()`")
    ctrl = start(proxy=_proxy)
    spec = _build_app_spec(app, name, route_prefix)
    # No deadline: deploy_app returns once every replica has finished
    # constructing (cold compile included) and raises the replica's own
    # error the moment one fails; a dead controller fails the get.
    rt.get(ctrl.deploy_app.remote(spec))
    handle = DeploymentHandle(name, spec["ingress"])
    if blocking:
        try:
            while True:
                time.sleep(1)
        except KeyboardInterrupt:
            pass
    return handle


def _build_app_spec(app: Application, name: str,
                    route_prefix: Optional[str]) -> dict:
    deployments: Dict[str, dict] = {}

    def visit(a: Application) -> str:
        d = a.deployment
        args = _strip(a.args)
        kwargs = _strip(a.kwargs)
        payload = cloudpickle.dumps((d.func_or_class, args, kwargs))
        if d.name in deployments:
            if deployments[d.name]["payload"] != payload:
                raise RayTpuError(
                    f"two different deployments named {d.name!r} in one app")
        else:
            deployments[d.name] = {"name": d.name, "payload": payload,
                                   "config": d.config}
        return d.name

    def _strip(obj):
        if isinstance(obj, Application):
            return _HandleMarker(visit(obj))
        if isinstance(obj, Deployment):
            raise RayTpuError(
                f"pass {obj!r} as an init arg via .bind(), not raw")
        if isinstance(obj, tuple):
            return tuple(_strip(x) for x in obj)
        if isinstance(obj, list):
            return [_strip(x) for x in obj]
        if isinstance(obj, dict):
            return {k: _strip(v) for k, v in obj.items()}
        return obj

    ingress = visit(app)
    # Streaming ingress detection (reference: StreamingResponse handling
    # in the proxy): a generator __call__ makes the proxy stream the
    # HTTP response chunked instead of buffering it.
    import inspect

    root = app.deployment.func_or_class
    target = root if inspect.isfunction(root) else \
        getattr(root, "__call__", None)
    stream = bool(target is not None and
                  (inspect.isgeneratorfunction(target)
                   or inspect.isasyncgenfunction(target)))
    return {"name": name, "route_prefix": route_prefix, "ingress": ingress,
            "stream": stream, "deployments": list(deployments.values())}


def get_app_handle(name: str = DEFAULT_APP_NAME) -> DeploymentHandle:
    ctrl = _controller()
    ingress = rt.get(ctrl.get_ingress.remote(name), timeout=10)
    if ingress is None:
        raise RayTpuError(f"no application named {name!r}")
    return DeploymentHandle(name, ingress)


def get_deployment_handle(deployment_name: str,
                          app_name: str = DEFAULT_APP_NAME
                          ) -> DeploymentHandle:
    return DeploymentHandle(app_name, deployment_name)


def status() -> dict:
    return rt.get(_controller().status.remote(), timeout=10)


def delete(name: str):
    rt.get(_controller().delete_app.remote(name), timeout=60)
    reset_routers()


def shutdown():
    """Tear down all apps, the proxy, and the controller. Serialized
    against an in-flight :func:`start` by the boot gate (so a teardown
    never interleaves a half-built control plane), with the teardown
    RPCs themselves OUTSIDE ``_client_lock`` — same rtsan RS104 fix as
    ``start``: the state lock is for the dict, never for the wire."""
    ev = _boot_enter()
    try:
        with _client_lock:
            ctrl = _client["controller"]
            proxy = _client["proxy"]
            _client.update({"controller": None, "proxy": None,
                            "http": None})
        if ctrl is None:
            try:
                ctrl = rt.get_actor(SERVE_CONTROLLER_NAME, timeout=0.5)
            except Exception:  # noqa: BLE001
                ctrl = None
        if ctrl is not None:
            try:
                rt.get(ctrl.shutdown_serve.remote(), timeout=60)
            except Exception:  # noqa: BLE001
                pass
            try:
                rt.kill(ctrl)
            except Exception:  # noqa: BLE001
                pass
        if proxy is None:
            # A fresh process (the CLI) has no cached handle — the
            # named actor is the source of truth.
            try:
                proxy = rt.get_actor("SERVE_PROXY", timeout=0.5)
            except Exception:  # noqa: BLE001 - no proxy running
                proxy = None
        if proxy is not None:
            try:
                rt.kill(proxy)
            except Exception:  # noqa: BLE001
                pass
    finally:
        _boot_exit(ev)
    reset_routers()


def _controller():
    with _client_lock:
        if _client["controller"] is not None:
            return _client["controller"]
    return rt.get_actor(SERVE_CONTROLLER_NAME, timeout=10)
