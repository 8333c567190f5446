"""Serve a service graph: in-process tasks or one OS process per replica.

In-process (`serve_graph`) is the test/dev path: every service instance
shares one event loop and one fabric. The CLI (`dynamo-tpu serve
pkg.module:Root`) is the production shape — it spawns `python -m
dynamo_tpu.sdk.serving pkg.module:Service` once per replica (the
reference's circus watcher per service, cli/serving.py:66,152), each
joining the fabric with its own lease so crash-detection and scaling work
exactly as for plain workers.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import logging
import sys
from typing import Any, AsyncIterator, Optional

from dynamo_tpu.runtime import DistributedRuntime, IngressServer
from dynamo_tpu.sdk.config import load_config
from dynamo_tpu.sdk.decorators import (
    ServiceMeta,
    service_dependencies,
    service_endpoints,
    service_meta,
)
from dynamo_tpu.sdk.graph import discover_graph

logger = logging.getLogger(__name__)


class _EndpointCaller:
    def __init__(self, client: "ServiceClient", ep_name: str):
        self._client = client
        self._ep = ep_name

    async def __call__(self, request: Any, context=None) -> AsyncIterator[Any]:
        router = await self._client._router(self._ep)
        async for item in router.generate(request, context=context):
            yield item

    async def unary(self, request: Any) -> Any:
        """Convenience: single-result endpoints — returns the last chunk."""
        last = None
        async for item in self(request):
            last = item
        return last


class ServiceClient:
    """depends() resolution: endpoint-name attribute access returns a
    streaming caller backed by a PushRouter over the target's instances."""

    def __init__(self, runtime: DistributedRuntime, meta: ServiceMeta):
        self._runtime = runtime
        self._meta = meta
        self._routers: dict[str, Any] = {}
        self._lock = asyncio.Lock()

    async def _router(self, ep_name: str):
        async with self._lock:
            router = self._routers.get(ep_name)
            if router is None:
                ep = (
                    self._runtime.namespace(self._meta.namespace)
                    .component(self._meta.name)
                    .endpoint(ep_name)
                )
                router = await ep.router()
                self._routers[ep_name] = router
        return router

    def __getattr__(self, name: str) -> _EndpointCaller:
        if name.startswith("_"):
            raise AttributeError(name)
        return _EndpointCaller(self, name)

    def close(self) -> None:
        for r in self._routers.values():
            r.close()


class ServiceHandle:
    """One running service instance (in this process)."""

    def __init__(
        self, runtime, instance, ingress, registrations, clients,
        owns_runtime: bool = True,
    ):
        self.runtime = runtime
        self.instance = instance
        self.ingress = ingress
        self.registrations = registrations
        self.clients = clients
        self.owns_runtime = owns_runtime

    async def stop(self) -> None:
        for reg in self.registrations:
            try:
                await reg.deregister()
            except Exception:
                logger.debug("deregister failed", exc_info=True)
        if self.ingress is not None:
            await self.ingress.stop()
        teardown = getattr(self.instance, "teardown", None)
        if teardown is not None:
            await teardown()
        for c in self.clients:
            c.close()
        if self.owns_runtime:
            await self.runtime.close()


async def start_service(
    cls,
    config: Optional[dict] = None,
    fabric_addr: Optional[str] = None,
    runtime: Optional[DistributedRuntime] = None,
) -> ServiceHandle:
    """Bring up ONE instance of `cls`: join the fabric, inject config and
    dependency clients, run optional `async setup()`, then register
    endpoints (ready-then-advertise: no consumer is routed here before
    setup finished). Pass `runtime` to share a caller-owned runtime (the
    handle then doesn't close it)."""
    meta = service_meta(cls)
    owns_runtime = runtime is None
    if runtime is None:
        runtime = await DistributedRuntime.create(fabric_addr)
    instance = cls()
    instance.config = dict(config or {})
    instance.runtime = runtime  # services may register workers/watchers

    clients = []
    for attr, dep in service_dependencies(cls).items():
        client = ServiceClient(runtime, dep.target_meta())
        setattr(instance, attr, client)
        clients.append(client)

    eps = service_endpoints(cls)
    ingress = None
    registrations = []
    try:
        if eps:
            ingress = IngressServer()
            for ep_name, attr in eps.items():
                ingress.add_handler(ep_name, getattr(instance, attr))
            await ingress.start()

        setup = getattr(instance, "setup", None)
        if setup is not None:
            await setup()

        advertise_host = instance.config.get("advertise_host", "127.0.0.1")
        for ep_name in eps:
            ep = (
                runtime.namespace(meta.namespace)
                .component(meta.name)
                .endpoint(ep_name)
            )
            registrations.append(
                await ep.register(advertise_host, ingress.port, metadata={})
            )
    except Exception:
        if ingress is not None:
            await ingress.stop()
        for c in clients:
            c.close()
        if owns_runtime:
            await runtime.close()
        raise
    logger.info(
        "service %s up (%d endpoints)", meta.name, len(eps)
    )
    return ServiceHandle(
        runtime, instance, ingress, registrations, clients,
        owns_runtime=owns_runtime,
    )


class GraphHandle:
    def __init__(self, handles: list[ServiceHandle], shared_fabric=None):
        self.handles = handles
        self.shared_fabric = shared_fabric

    def instance_of(self, cls) -> Any:
        for h in self.handles:
            if isinstance(h.instance, cls):
                return h.instance
        raise KeyError(cls)

    async def stop(self) -> None:
        for h in reversed(self.handles):  # consumers before providers
            await h.stop()
        if self.shared_fabric is not None:
            await self.shared_fabric.close()


async def serve_graph(
    root,
    config: Optional[dict[str, dict]] = None,
    fabric_addr: Optional[str] = None,
    static: bool = False,
) -> GraphHandle:
    """In-process serving: every service of the graph on this event loop,
    dependencies first. `static=True` runs without any fabric server — all
    services share ONE in-memory fabric (discovery stays coherent). On any
    start failure, already-started services are stopped before the error
    propagates."""
    config = config or {}
    shared_fabric = None
    runtimes: list[Optional[DistributedRuntime]] = []
    classes = discover_graph(root)
    if static:
        from dynamo_tpu.runtime.fabric.local import LocalFabric

        shared_fabric = LocalFabric()
        for _ in classes:
            # LocalFabric has a real expiry reaper but no keepalive loop
            # (that lives in RemoteFabric) — an effectively-infinite TTL
            # keeps static in-process graphs registered for their lifetime.
            lease = await shared_fabric.grant_lease(1e12)
            runtimes.append(DistributedRuntime(shared_fabric, primary_lease=lease))
    else:
        runtimes = [None] * len(classes)

    handles: list[ServiceHandle] = []
    try:
        for cls, rt in zip(classes, runtimes):
            meta = service_meta(cls)
            handles.append(
                await start_service(
                    cls, config.get(meta.name), fabric_addr, runtime=rt
                )
            )
    except Exception:
        for h in reversed(handles):
            try:
                await h.stop()
            except Exception:
                logger.debug("rollback stop failed", exc_info=True)
        if shared_fabric is not None:
            await shared_fabric.close()
        raise
    return GraphHandle(handles, shared_fabric=shared_fabric)


def resolve_service(spec: str):
    """'pkg.module:ClassName' -> class."""
    mod_name, _, cls_name = spec.partition(":")
    if not cls_name:
        raise ValueError(f"service spec {spec!r} must be module:Class")
    mod = importlib.import_module(mod_name)
    return getattr(mod, cls_name)


async def _amain(args) -> None:
    import json
    import os

    cls = resolve_service(args.service)
    meta = service_meta(cls)
    if args.config:
        svc_config = load_config(args.config).get(meta.name)
    else:
        # k8s containers rendered by `deploy` carry the frozen per-service
        # config in the environment (sdk/build.py render_k8s).
        env_cfg = os.environ.get("DYNTPU_SERVICE_CONFIG")
        svc_config = json.loads(env_cfg) if env_cfg else None
    handle = await start_service(cls, svc_config, args.fabric)
    print(f"service {meta.name} up", flush=True)
    try:
        await asyncio.Event().wait()
    finally:
        await handle.stop()


def main(argv: Optional[list[str]] = None) -> None:
    p = argparse.ArgumentParser(
        prog="python -m dynamo_tpu.sdk.serving",
        description="run ONE service of a graph (spawned by `dynamo-tpu serve`)",
    )
    p.add_argument("service", help="pkg.module:ClassName")
    p.add_argument("--fabric", required=True)
    p.add_argument("-f", "--config", default=None)
    args = p.parse_args(argv)
    from dynamo_tpu.logging_config import configure_logging

    configure_logging()
    asyncio.run(_amain(args))


if __name__ == "__main__":
    main()
