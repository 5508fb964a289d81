"""App core: construction, config load, tracer init, route registration,
concurrent server startup.

Parity: /root/reference/pkg/gofr/gofr.go —
- ``new()`` (:49): read config, build container, init tracer, prepare HTTP
  (port from HTTP_PORT|8000, :57-62) and gRPC (GRPC_PORT|9000, :65-70);
- ``new_cmd()`` (:76): config + container + tracer, no servers;
- ``run()`` (:90-126): default routes (health/favicon/catch-all, :102-107),
  servers started concurrently, blocks until shutdown;
- route helpers GET/PUT/POST/DELETE (:152-169), ``add_http_service``
  (:139-149), ``sub_command`` (:181), ``register_service`` for gRPC (:42).

Improvement over the reference (SURVEY.md §5 notes it lacks graceful
shutdown): SIGINT/SIGTERM drain servers and close the container.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Optional

from gofr_tpu.config import Config, EnvFileConfig
from gofr_tpu.container import Container
from gofr_tpu.context import Context
from gofr_tpu.handler import (
    Handler,
    catch_all_handler,
    adapter_load_handler,
    adapter_unload_handler,
    adapters_list_handler,
    anomalies_admin_handler,
    dispatches_admin_handler,
    engine_admin_handler,
    favicon_handler,
    health_handler,
    kv_export_handler,
    make_endpoint,
    metrics_handler,
    overview_admin_handler,
    postmortem_list_handler,
    postmortem_trigger_handler,
    profiler_start_handler,
    profiler_status_handler,
    profiler_stop_handler,
    ready_handler,
    requests_admin_handler,
    slo_admin_handler,
    slo_budget_handler,
    tenants_admin_handler,
    timeseries_admin_handler,
)
from gofr_tpu.http.middleware import (
    cors_middleware,
    logging_middleware,
    metrics_middleware,
    tracer_middleware,
)
from gofr_tpu.http.router import Router
from gofr_tpu.http.server import HTTPServer, LoopClock
from gofr_tpu.tracing import init_tracer

DEFAULT_HTTP_PORT = 8000  # parity: pkg/gofr/default.go:3-6
DEFAULT_GRPC_PORT = 9000


class App:
    def __init__(self, configs_dir: Optional[str] = None, cmd_app: bool = False):
        self.config: Config = EnvFileConfig(configs_dir or "./configs")
        self.container = Container(self.config)
        self.logger = self.container.logger
        self.tracer = init_tracer(self.config, self.logger)
        # exporter drops become a counter an alert can watch (the
        # exporter exists before the registry, so it is attached here)
        attach = getattr(self.tracer.exporter, "attach_metrics", None)
        if attach is not None:
            attach(self.container.metrics)
        self._cmd_app = cmd_app
        self._cmd_routes: list[tuple[str, Handler]] = []
        self._grpc_registrations: list[tuple[Any, Any]] = []
        self._grpc_json_services: dict[str, dict[str, Handler]] = {}
        self._grpc_json_stream_services: dict[str, dict[str, Handler]] = {}
        self._grpc_server: Optional[Any] = None
        self.http_server: Optional[HTTPServer] = None

        self.router = Router()
        if not cmd_app:
            self.http_port = int(self.config.get_or_default("HTTP_PORT", str(DEFAULT_HTTP_PORT)))
            self.grpc_port = int(self.config.get_or_default("GRPC_PORT", str(DEFAULT_GRPC_PORT)))
            # middleware chain, outermost first (parity: http/router.go:19-23)
            self.router.use(
                tracer_middleware,
                logging_middleware(self.logger),
                metrics_middleware(self.container.metrics),
                cors_middleware,
            )

    # -- route registration (parity: gofr.go:152-169) ------------------------
    def get(self, pattern: str, handler: Handler) -> None:
        self.add_route("GET", pattern, handler)

    def post(self, pattern: str, handler: Handler) -> None:
        self.add_route("POST", pattern, handler)

    def put(self, pattern: str, handler: Handler) -> None:
        self.add_route("PUT", pattern, handler)

    def patch(self, pattern: str, handler: Handler) -> None:
        self.add_route("PATCH", pattern, handler)

    def delete(self, pattern: str, handler: Handler) -> None:
        self.add_route("DELETE", pattern, handler)

    def add_route(self, method: str, pattern: str, handler: Handler) -> None:
        self.router.add(method, pattern, make_endpoint(handler, self.container))

    # -- inter-service clients (parity: gofr.go:139-149) ---------------------
    def add_http_service(self, name: str, address: str) -> None:
        from gofr_tpu.service import new_http_service

        self.container.services[name] = new_http_service(address, self.logger, name=name)

    # -- gRPC (parity: gofr.go:42-46) ----------------------------------------
    def register_service(self, add_to_server: Callable, servicer: Any) -> None:
        """Register a generated-stub gRPC service: ``add_to_server`` is the
        protoc-generated ``add_XServicer_to_server`` callable."""
        self._grpc_registrations.append((add_to_server, servicer))

    def register_json_service(
        self,
        service_name: str,
        methods: dict[str, Handler],
        stream_methods: Optional[dict[str, Handler]] = None,
    ) -> None:
        """Register a reflection-free JSON-over-gRPC service: each method is
        a transport-agnostic ``handler(ctx)`` (TPU-native addition for
        serving without protoc codegen). ``stream_methods`` handlers return
        an iterator; each item becomes one JSON message on a server stream
        (token decode, BASELINE.md config 4). A name appearing in both maps
        is rejected here, at registration time."""
        overlap = set(methods) & set(stream_methods or {})
        if overlap:
            raise ValueError(
                f"service '{service_name}' registers {sorted(overlap)} as both "
                "unary and streaming — a method must be one or the other"
            )
        if methods:
            self._grpc_json_services[service_name] = methods
        if stream_methods:
            self._grpc_json_stream_services[service_name] = stream_methods

    # -- CLI (parity: gofr.go:181, cmd.go:54-63) -----------------------------
    def sub_command(self, pattern: str, handler: Handler) -> None:
        self._cmd_routes.append((pattern, handler))

    # -- run ------------------------------------------------------------------
    def _install_default_routes(self) -> None:
        # parity: gofr.go:102-107
        self.router.add("GET", "/.well-known/health", make_endpoint(health_handler, self.container))
        self.router.add("GET", "/.well-known/ready", make_endpoint(ready_handler, self.container))
        self.router.add("GET", "/favicon.ico", make_endpoint(favicon_handler, self.container))
        self.router.add("GET", "/metrics", make_endpoint(metrics_handler, self.container))
        # device profiler admin surface (off the serving hot path)
        self.router.add("GET", "/admin/profiler",
                        make_endpoint(profiler_status_handler, self.container))
        self.router.add("POST", "/admin/profiler/start",
                        make_endpoint(profiler_start_handler, self.container))
        self.router.add("POST", "/admin/profiler/stop",
                        make_endpoint(profiler_stop_handler, self.container))
        # request flight recorder admin surface (telemetry.py)
        self.router.add("GET", "/admin/requests",
                        make_endpoint(requests_admin_handler, self.container))
        self.router.add("GET", "/admin/slo",
                        make_endpoint(slo_admin_handler, self.container))
        # SLO engine (slo.py): error budgets + burn-rate alerting; and
        # the bounded per-tenant usage sketch (telemetry.TenantLedger)
        self.router.add("GET", "/admin/slo/budget",
                        make_endpoint(slo_budget_handler, self.container))
        self.router.add("GET", "/admin/tenants",
                        make_endpoint(tenants_admin_handler, self.container))
        # engine introspection (tpu/introspect.py): the layer below the
        # flight recorder — engine state, boot/compile timeline, and the
        # device dispatch timeline
        self.router.add("GET", "/admin/engine",
                        make_endpoint(engine_admin_handler, self.container))
        self.router.add("GET", "/admin/dispatches",
                        make_endpoint(dispatches_admin_handler, self.container))
        # the SLO engine's burn-alert evidence ring (anomaly.py)
        self.router.add("GET", "/admin/anomalies",
                        make_endpoint(anomalies_admin_handler, self.container))
        # telemetry timebase (timebase.py): retained metric history +
        # the one-page ops rollup; postmortem black box (postmortem.py)
        self.router.add("GET", "/admin/timeseries",
                        make_endpoint(timeseries_admin_handler, self.container))
        self.router.add("GET", "/admin/overview",
                        make_endpoint(overview_admin_handler, self.container))
        self.router.add("GET", "/admin/postmortem",
                        make_endpoint(postmortem_list_handler, self.container))
        self.router.add("POST", "/admin/postmortem",
                        make_endpoint(postmortem_trigger_handler, self.container))
        # cross-replica KV transfer (disaggregated prefill/decode):
        # peers pull cached paged-KV block tables by prompt hash
        self.router.add("GET", "/admin/kv/{hash}",
                        make_endpoint(kv_export_handler, self.container))
        self.router.add("GET", "/admin/adapters",
                        make_endpoint(adapters_list_handler, self.container))
        self.router.add("POST", "/admin/adapters",
                        make_endpoint(adapter_load_handler, self.container))
        self.router.add("DELETE", "/admin/adapters/{name}",
                        make_endpoint(adapter_unload_handler, self.container))
        self.router.set_not_found(make_endpoint(catch_all_handler, self.container))

    def run(self) -> None:
        """Blocking run (parity: gofr.go:90-126)."""
        if self._cmd_app:
            from gofr_tpu.cmd import run_cmd

            code = run_cmd(self)
            if code != 0:
                raise SystemExit(code)
            return
        self.start()
        stop = threading.Event()
        try:
            import signal

            signal.signal(signal.SIGTERM, lambda *_: stop.set())
        except (ValueError, OSError):
            pass  # not the main thread; SIGTERM keeps default handling
        try:
            stop.wait()
            self.logger.info("SIGTERM received, shutting down")
        except KeyboardInterrupt:
            self.logger.info("shutting down")
        finally:
            self.shutdown()

    def start(self) -> "App":
        """Start servers in background threads and return (test/bench shape;
        the reference achieves the same with goroutines + WaitGroup,
        gofr.go:109-125)."""
        self._install_default_routes()
        self.http_server = HTTPServer(
            self.router, self.http_port, self.logger, loop_clock=self._loop_clock()
        )
        self.http_server.run_in_thread()
        if (
            self._grpc_registrations
            or self._grpc_json_services
            or self._grpc_json_stream_services
        ):
            from gofr_tpu.grpcx import GRPCServer

            self._grpc_server = GRPCServer(
                self.grpc_port,
                self.container,
                registrations=self._grpc_registrations,
                json_services=self._grpc_json_services,
                json_stream_services=self._grpc_json_stream_services,
            )
            self._grpc_server.start()
        return self

    def _loop_clock(self) -> LoopClock:
        """The server's event-loop clock, wired to what reads it: the
        metrics registry, the flight recorder (a record's loop lag over its
        own life, ``http`` on /admin/engine) and, for the line a late tick
        logs, the device dispatches then in flight."""
        container = self.container

        def running() -> Any:
            timeline = getattr(container.tpu, "timeline", None)
            return timeline.running() if timeline is not None else None

        clock = LoopClock(
            histogram=container.metrics.histogram(
                "gofr_tpu_http_loop_lag_seconds",
                "how late the HTTP server's event loop woke from a 50 ms "
                "sleep: the wait of every frame and request behind "
                "whatever held the loop's thread",
                buckets=(0.0005, 0.001, 0.002, 0.005, 0.01, 0.025, 0.05,
                         0.1, 0.25, 0.5, 1.0, 2.5, 5.0),
            ),
            logger=self.logger, running=running,
            ready=lambda: container.tpu is None or container.tpu.ready(),
        )
        container.telemetry.loop_clock = clock
        return clock

    def shutdown(self) -> None:
        # visible to in-flight stream teardown: asyncio acloses every
        # suspended response generator on shutdown, and those aborts
        # must not count as client_abort cancellations (no client left)
        self.container.closing = True
        fleet = getattr(self.container, "fleet", None)
        if fleet is not None:
            # graceful drain BEFORE the listener stops: admission closes
            # (new requests shed 503, readiness flips) while in-flight
            # requests finish through the still-running server
            timeout = float(
                self.config.get_or_default("FLEET_DRAIN_TIMEOUT_S", "10")
            )
            fleet.drain(timeout_s=timeout)
        if self.http_server:
            self.http_server.shutdown()
        if self._grpc_server:
            self._grpc_server.stop()
        self.container.close()
        self.tracer.shutdown()


def new(configs_dir: Optional[str] = None) -> App:
    """Parity: gofr.go:49."""
    return App(configs_dir=configs_dir)


def new_cmd(configs_dir: Optional[str] = None) -> App:
    """Parity: gofr.go:76."""
    return App(configs_dir=configs_dir, cmd_app=True)
