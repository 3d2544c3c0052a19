"""HTTP ingress: in-driver (test) and worker-hosted (deployable).

Reference: ``python/ray/serve/_private/proxy.py`` (uvicorn/starlette
proxy actors, streaming responses over chunked transfer) [UNVERIFIED —
mount empty, SURVEY.md §0].

Two placements of one ingress, the event loop in ``ingress.py``
(``AsyncIngress``), which also holds the typed-error mapping
(``classify_error``, ``terminal_record``):

- ``HttpProxy``: ingress in the driver process — zero-setup for tests
  and notebooks.
- ``ProxyActor``: the same ingress hosted in a WORKER process (the
  reference's proxy-actor topology): HTTP parsing/serialization runs
  off the driver's threads, and the controller pushes route-table
  updates to it as replica membership changes.

Overload (docs/serve.md): a shed at the router — the deployment's
queue hit ``max_queued_requests`` — surfaces as the PR-3
``BackpressureError``; the handler maps it to **503 + Retry-After**
so well-behaved clients back off instead of hammering a saturated
tier.

Shutdown is deterministic: both placements count in-flight requests
and ``shutdown``/``prepare_shutdown`` stop the listener, then wait
(bounded) for that count to drain before closing the socket — an
in-flight request races neither the socket teardown nor (for the
worker proxy) the ``ray_tpu.kill``.

Streaming: ``POST /<deployment>?stream=1`` (or the
``X-RTPU-Stream: 1`` header / ``Accept: text/event-stream``) responds
with chunked transfer encoding — one JSON line per yielded item,
written as the replica produces them.
"""

from __future__ import annotations

import logging
import threading

from ray_tpu.serve._private.ingress import AsyncIngress

logger = logging.getLogger(__name__)


class HttpProxy:
    """In-driver ingress (tests/notebooks)."""

    def __init__(self, controller, host: str = "127.0.0.1", port: int = 0):
        self._controller = controller
        self._server = AsyncIngress(controller.get_replica_set,
                                    controller.status,
                                    host=host, port=port)
        self.address = self._server.address

    def shutdown(self, drain_timeout_s: float = 10.0) -> None:
        """Deterministic teardown: stop accepting, DRAIN in-flight
        requests (bounded), then close the socket — a request in
        flight during shutdown gets its response instead of a reset
        socket."""
        try:
            left = self._server.drain(drain_timeout_s)
            if left:
                logger.warning(
                    "http proxy closed with %d requests still in "
                    "flight after %.0fs drain", left, drain_timeout_s)
            self._server.server_close()
        except Exception:
            pass    # double-shutdown / already-closed socket


class ProxyActor:
    """Worker-hosted ingress: the HTTP server lives in this actor's
    worker process, so request parsing/serialization never contends
    with the driver's scheduling threads. The controller pushes
    ``update_routes`` whenever a deployment's replica membership
    changes (the pushed ReplicaSet pickles as a snapshot with fresh
    local in-flight counts)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        self._routes = {}            # name -> ReplicaSet snapshot
        self._lock = threading.Lock()
        self._server = AsyncIngress(self._get_replica_set,
                                    self._status,
                                    host=host, port=port)
        self._addr = self._server.address

    def _get_replica_set(self, name: str):
        with self._lock:
            return self._routes.get(name)

    def _status(self) -> dict:
        with self._lock:
            return {name: {"live_replicas": rs.num_replicas(),
                           "ongoing_requests": rs.total_inflight()}
                    for name, rs in self._routes.items()}

    def ongoing(self, name: str) -> int:
        """In-flight requests this proxy currently has against one
        deployment (the controller aggregates these into its
        autoscaling signal — proxy traffic is otherwise invisible to
        the driver-side ReplicaSet)."""
        with self._lock:
            rs = self._routes.get(name)
        return rs.total_inflight() if rs is not None else 0

    def update_routes(self, name: str, replica_set) -> str:
        """Controller push: replace (or drop, when None) one
        deployment's routing snapshot."""
        with self._lock:
            if replica_set is None:
                self._routes.pop(name, None)
            else:
                self._routes[name] = replica_set
        return "ok"

    def prepare_shutdown(self, drain_timeout_s: float = 10.0) -> int:
        """serve.shutdown step 2: stop accepting and drain in-flight
        HTTP requests while replicas are still alive — the subsequent
        ``ray_tpu.kill`` then hits an idle actor, never a request in
        flight. Returns how many handlers were still running at the
        drain deadline (0 = clean)."""
        left = self._server.drain(drain_timeout_s)
        try:
            self._server.server_close()
        except Exception:  # noqa: BLE001
            pass    # socket already closed
        return left

    def address(self):
        return tuple(self._addr)

    def ping(self) -> str:
        return "pong"
