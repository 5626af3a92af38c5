"""ray_tpu.serve: model serving — controller, replicas, router, batching.

Reference surface: python/ray/serve (serve.run/deployment/delete,
controller.py:80, router.py:281, replica.py:520, batching.py). Replicas
wrap jitted predict callables; @serve.batch's bucket_sizes keep batch
shapes XLA-static.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Dict, Optional

import ray_tpu
from ray_tpu.serve.batching import (
    batch,
    bucket_pad_size,
    continuous_batch,
    shutdown_batchers,
)
from ray_tpu.serve.multiplex import (
    fetch_model,
    get_multiplexed_model_id,
    list_models,
    multiplexed,
    register_model,
)
from ray_tpu.serve.controller import CONTROLLER_NAME, ServeController
from ray_tpu.serve.handle import (
    BackPressureError,
    DeploymentHandle,
    DeploymentResponse,
)
from ray_tpu.serve.proxy import HTTPProxy

logger = logging.getLogger(__name__)

__all__ = [
    "Application",
    "BackPressureError",
    "Deployment",
    "DeploymentHandle",
    "DeploymentResponse",
    "HTTPProxy",
    "apply",
    "DAGDriver",
    "InputNode",
    "batch",
    "bucket_pad_size",
    "build",
    "build_graph",
    "continuous_batch",
    "delete",
    "deployment",
    "fetch_model",
    "get_deployment_handle",
    "get_multiplexed_model_id",
    "list_models",
    "llm",
    "multiplexed",
    "register_model",
    "run",
    "run_graph",
    "shutdown",
    "shutdown_batchers",
    "start_http_proxy",
    "status",
]


class Deployment:
    def __init__(self, func_or_class, name: str, config: Dict[str, Any]):
        self.func_or_class = func_or_class
        self.name = name
        self.config = config

    # public kwarg -> internal config key (same remapping deployment() does)
    _OPTION_KEYS = {
        "autoscaling_config": "autoscaling",
        "ray_actor_options": "resources",
    }

    def options(self, **overrides) -> "Deployment":
        cfg = {
            **self.config,
            **{self._OPTION_KEYS.get(k, k): v for k, v in overrides.items()},
        }
        name = cfg.pop("name", self.name)
        unknown = set(cfg) - {
            "num_replicas", "user_config", "autoscaling", "resources",
            "max_concurrent_queries", "max_queued_requests", "drain_grace_s",
            "slo_p99_s", "slo_availability", "slo_ttft_p99_s",
        }
        if unknown:
            raise TypeError(f"unknown deployment options: {sorted(unknown)}")
        return Deployment(self.func_or_class, name, cfg)

    def bind(self, *init_args, **init_kwargs) -> "Application":
        return Application(self, init_args, init_kwargs)


#: a replica's executing slots where neither the deployment nor its callable says
MAX_CONCURRENT_QUERIES = 8


def _executing_slots(dep: Deployment, init_args, init_kwargs) -> int:
    """A replica's executing slots: ``max_concurrent_queries`` where the
    deployment names it; else ``MAX_CONCURRENT_QUERIES``, or what its callable,
    bound with these arguments, says it runs at once (``concurrent_queries``)
    where that is more."""
    asked = dep.config.get("max_concurrent_queries")
    if asked is not None:
        return int(asked)
    own = getattr(dep.func_or_class, "concurrent_queries", None)
    return max(
        MAX_CONCURRENT_QUERIES, int(own(*init_args, **init_kwargs)) if own is not None else 0)


class Application:
    def __init__(self, deployment_obj: Deployment, init_args, init_kwargs):
        self.deployment = deployment_obj
        self.init_args = init_args
        self.init_kwargs = init_kwargs

    def __getattr__(self, name):
        # dotted method binding for the deployment-graph DAG API
        # (serve/dag.py): ``app.method.bind(args)`` builds a MethodNode.
        # Defined on the class itself so behavior never depends on whether
        # dag.py was imported. Private/dunder names raise normally (pickle
        # and hasattr-probing code paths stay sane); a public name that is
        # NOT a method of the wrapped class also raises, so typos fail at
        # authoring time instead of surfacing as broken graph nodes.
        if name.startswith("_"):
            raise AttributeError(name)
        target = self.deployment.func_or_class
        if not callable(getattr(target, name, None)):
            raise AttributeError(
                f"{target!r} has no method {name!r} to bind"
            )
        from ray_tpu.serve.dag import _MethodBinder

        return _MethodBinder(self, name)


def deployment(
    _func_or_class=None,
    *,
    name: Optional[str] = None,
    num_replicas: int = 1,
    user_config: Any = None,
    autoscaling_config: Optional[Dict[str, Any]] = None,
    ray_actor_options: Optional[Dict[str, Any]] = None,
    max_concurrent_queries: Optional[int] = None,
    max_queued_requests: Optional[int] = None,
    drain_grace_s: float = 30.0,
    slo_p99_s: Optional[float] = None,
    slo_availability: Optional[float] = None,
    slo_ttft_p99_s: Optional[float] = None,
):
    """``@serve.deployment`` decorator (reference: serve/api.py deployment).

    ``max_concurrent_queries`` is the per-replica executing-slot count
    (the replica actor's concurrency). Left out, it is ``MAX_CONCURRENT_QUERIES``
    (8), or what the callable says it runs at once as it is bound where that is
    more (``concurrent_queries(*init_args, **init_kwargs)``, a static method:
    ``serve.llm.LLMServer`` answers with its engine's largest lane bucket).
    ``max_queued_requests`` bounds the
    admission queue beyond those slots — excess requests shed with
    :class:`BackPressureError` (503 + Retry-After at the proxy). ``None``
    defaults the queue allowance to one full round of executing slots.
    ``drain_grace_s`` is how long a scaled-down replica may finish
    in-flight work before a forced kill.

    ``slo_p99_s`` / ``slo_availability`` override the default
    per-deployment SLO rule targets (``ray_tpu.slo``); the cluster-wide
    defaults come from ``serve_slo_default_p99_s`` /
    ``serve_slo_default_availability`` (``serve_default_slos=False``
    disables the automatic rules entirely). ``slo_ttft_p99_s`` — for LLM
    deployments (``serve.llm``) — additionally auto-registers a
    ``serve-<name>-ttft-p99`` rule over the time-to-first-token
    histogram."""

    def deco(target):
        return Deployment(
            target,
            name or getattr(target, "__name__", "deployment"),
            {
                "num_replicas": num_replicas,
                "user_config": user_config,
                "autoscaling": autoscaling_config,
                "resources": ray_actor_options,
                "max_concurrent_queries": max_concurrent_queries,
                "max_queued_requests": max_queued_requests,
                "drain_grace_s": drain_grace_s,
                "slo_p99_s": slo_p99_s,
                "slo_availability": slo_availability,
                "slo_ttft_p99_s": slo_ttft_p99_s,
            },
        )

    return deco if _func_or_class is None else deco(_func_or_class)


def _get_or_create_controller():
    try:
        return ray_tpu.get_actor(CONTROLLER_NAME)
    except Exception:
        pass
    try:
        return ServeController.options(name=CONTROLLER_NAME, max_restarts=1).remote()
    except Exception:
        # lost the create race: someone else made it
        return ray_tpu.get_actor(CONTROLLER_NAME)


def _deploy_tree(app: Application, controller, timeout: float,
                 deployed: Dict[int, Any],
                 name_override: Optional[str] = None) -> DeploymentHandle:
    """Deploy an Application and, first, every Application bound into its
    init args — model composition (reference: serve deployment graphs,
    serve/deployment_graph.py): a deployment receives live
    DeploymentHandles where its constructor was bound child apps.

    ``deployed`` maps id(app) -> (app, handle); storing the app keeps it
    alive so a freed temporary's id can't be reused by a sibling."""
    if id(app) in deployed:
        return deployed[id(app)][1]

    def _sub(v):
        if isinstance(v, Application):
            return _deploy_tree(v, controller, timeout, deployed)
        if isinstance(v, Deployment):
            return _deploy_tree(v.bind(), controller, timeout, deployed)
        return v

    init_args = tuple(_sub(a) for a in app.init_args)
    init_kwargs = {k: _sub(v) for k, v in app.init_kwargs.items()}
    dep = app.deployment
    dep_name = name_override or dep.name
    spec = {
        "func_or_class": dep.func_or_class,
        "init_args": init_args,
        "init_kwargs": init_kwargs,
        **dep.config,
        "max_concurrent_queries": _executing_slots(dep, app.init_args, app.init_kwargs),
    }
    ray_tpu.get(controller.deploy.remote(dep_name, spec), timeout=timeout)
    handle = DeploymentHandle(dep_name)
    deployed[id(app)] = (app, handle)
    return handle


def run(target, *, name: Optional[str] = None, wait_for_replicas: bool = True,
        timeout: float = 60.0) -> DeploymentHandle:
    """Deploy an Application (or bare Deployment) and return its handle.
    Applications bound as init args deploy first (composition)."""
    if isinstance(target, Deployment):
        target = target.bind()
    if not isinstance(target, Application):
        raise TypeError(f"serve.run expects an Application/Deployment, got {target!r}")
    controller = _get_or_create_controller()
    deployed: Dict[int, Any] = {}
    handle = _deploy_tree(target, controller, timeout, deployed, name)
    if wait_for_replicas:
        import time as _time

        deadline = _time.monotonic() + timeout
        for _app, h in deployed.values():
            while True:
                table = ray_tpu.get(
                    controller.get_routing_table.remote(h.deployment_name),
                    timeout=30,
                )
                if table and table["replicas"]:
                    break
                if _time.monotonic() >= deadline:
                    raise TimeoutError(
                        f"deployment {h.deployment_name!r} has no replicas "
                        f"after {timeout}s (insufficient cluster resources?)"
                    )
                _time.sleep(0.05)
    return handle


def get_deployment_handle(name: str) -> DeploymentHandle:
    return DeploymentHandle(name)


def status() -> Dict[str, Any]:
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    return ray_tpu.get(controller.status.remote(), timeout=30)


def delete(name: str, timeout: float = 30.0) -> bool:
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    return ray_tpu.get(controller.delete_deployment.remote(name), timeout=timeout)


def shutdown(timeout: float = 30.0):
    try:
        controller = ray_tpu.get_actor(CONTROLLER_NAME)
    except Exception:
        return
    try:
        ray_tpu.get(controller.shutdown.remote(), timeout=timeout)
    finally:
        try:
            ray_tpu.kill(controller)
        except Exception:
            pass


def start_http_proxy(host: str = "127.0.0.1", port: int = 0,
                     max_total_inflight: int = 1024) -> HTTPProxy:
    """Start an in-driver HTTP ingress (POST /<deployment> with JSON).
    ``max_total_inflight`` bounds requests admitted across ALL routes;
    beyond it the proxy sheds with 503 + Retry-After."""
    return HTTPProxy(host, port, max_total_inflight=max_total_inflight)


# -- declarative config (reference: serve/schema.py ServeDeploySchema +
#    `serve build`/`serve deploy`) ------------------------------------------


def build(target, name: Optional[str] = None) -> Dict[str, Any]:
    """Render an Application DAG into a JSON-able deploy config.

    Each deployment's callable must be importable (``module:qualname``);
    bound child applications appear as ``{"$handle": <name>}`` placeholders
    in init args. The result round-trips through :func:`apply`."""
    if isinstance(target, Deployment):
        target = target.bind()
    deployments: list = []
    # id(app) -> (app, name): the app reference pins the object so a freed
    # temporary's id can't alias a sibling
    seen: Dict[int, Any] = {}

    def _walk(app: Application, name_override=None) -> str:
        if id(app) in seen:
            return seen[id(app)][1]
        dep = app.deployment
        dep_name = name_override or dep.name
        seen[id(app)] = (app, dep_name)
        fc = dep.func_or_class
        module = getattr(fc, "__module__", None)
        qualname = getattr(fc, "__qualname__", None)
        if not module or not qualname or "<locals>" in qualname:
            raise ValueError(
                f"deployment {dep_name!r} callable is not importable "
                f"({module}:{qualname}); define it at module top level"
            )

        def _enc(v):
            if isinstance(v, Application):
                return {"$handle": _walk(v)}
            if isinstance(v, Deployment):
                return {"$handle": _walk(v.bind())}
            return v

        deployments.append({
            "name": dep_name,
            "import_path": f"{module}:{qualname}",
            "init_args": [_enc(a) for a in app.init_args],
            "init_kwargs": {k: _enc(v) for k, v in app.init_kwargs.items()},
            "num_replicas": dep.config.get("num_replicas", 1),
            "user_config": dep.config.get("user_config"),
            "autoscaling_config": dep.config.get("autoscaling"),
            "resources": dep.config.get("resources"),
            "max_concurrent_queries": _executing_slots(
                dep, app.init_args, app.init_kwargs),
            "max_queued_requests": dep.config.get("max_queued_requests"),
            "drain_grace_s": dep.config.get("drain_grace_s", 30.0),
        })
        return dep_name

    ingress = _walk(target, name)
    return {"ingress": ingress, "deployments": deployments}


def apply(config: Dict[str, Any], *, timeout: float = 60.0) -> DeploymentHandle:
    """Deploy from a config produced by :func:`build` (or hand-written)."""
    import importlib

    controller = _get_or_create_controller()
    handles: Dict[str, DeploymentHandle] = {}

    def _dec(v):
        if isinstance(v, dict) and set(v) == {"$handle"}:
            return DeploymentHandle(v["$handle"])
        return v

    # children first: deployments referenced via $handle must exist by the
    # time their parent's constructor runs
    by_name = {d["name"]: d for d in config["deployments"]}
    resolved: set = set()

    def _deploy(name: str):
        if name in resolved:
            return
        d = by_name[name]
        for v in (*d.get("init_args", ()), *d.get("init_kwargs", {}).values()):
            if isinstance(v, dict) and set(v) == {"$handle"}:
                _deploy(v["$handle"])
        module, qualname = d["import_path"].split(":")
        target = importlib.import_module(module)
        for part in qualname.split("."):
            target = getattr(target, part)
        if isinstance(target, Deployment):
            target = target.func_or_class
        spec = {
            "func_or_class": target,
            "init_args": tuple(_dec(a) for a in d.get("init_args", ())),
            "init_kwargs": {k: _dec(v) for k, v in d.get("init_kwargs", {}).items()},
            "num_replicas": d.get("num_replicas", 1),
            "user_config": d.get("user_config"),
            "autoscaling": d.get("autoscaling_config"),
            "resources": d.get("resources"),
            "max_concurrent_queries": d.get("max_concurrent_queries", MAX_CONCURRENT_QUERIES),
            "max_queued_requests": d.get("max_queued_requests"),
            "drain_grace_s": d.get("drain_grace_s", 30.0),
        }
        ray_tpu.get(controller.deploy.remote(name, spec), timeout=timeout)
        handles[name] = DeploymentHandle(name)
        resolved.add(name)

    for d in config["deployments"]:
        _deploy(d["name"])
    # hand-written configs (serve CLI) may omit "ingress": default to the
    # first deployment, matching the file's declaration order
    ingress = config.get("ingress") or config["deployments"][0]["name"]
    return handles[ingress]


# explicit deployment-graph API (reference: serve/deployment_graph.py)
from ray_tpu.serve.dag import (  # noqa: E402
    DAGDriver,
    InputNode,
    build as build_graph,
    run_graph,
)


def __getattr__(name: str):
    # ``serve.llm`` loads lazily: it pulls in jax, which most serve users
    # (and the serve test matrix) never need at import time
    if name == "llm":
        import importlib

        mod = importlib.import_module("ray_tpu.serve.llm")
        globals()["llm"] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
