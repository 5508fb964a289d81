"""Dependency-injection container: logger, config, datasources, service
clients, metrics, and the TPU device.

Parity: /root/reference/pkg/gofr/container/container.go:19-95 — config-driven
conditional wiring (Redis when REDIS_HOST, SQL when DB host/name configured,
:48-86), connect errors logged but NEVER fatal (the app runs degraded,
:60-64, :80-85), health aggregation (:26-38), ``GetHTTPService`` (:93).
TPU-native additions: a ``tpu`` member wired from TPU_*/MODEL_* config keys
and a metrics registry (the reference has none, SURVEY.md §5).
"""

from __future__ import annotations

from typing import Any, Optional

from gofr_tpu.anomaly import AnomalyRing
from gofr_tpu.config import Config
from gofr_tpu.datasource.health import DOWN, UP, Health
from gofr_tpu.logging import new_logger
from gofr_tpu.metrics import Registry
from gofr_tpu.postmortem import PostmortemStore
from gofr_tpu.slo import DEFAULT_TARGETS, SloEngine
from gofr_tpu.telemetry import FlightRecorder, TenantLedger, exemplar_provider
from gofr_tpu.timebase import TimebaseSampler


class Container:
    def __init__(self, config: Config, wire: bool = True):
        self.config = config
        self.logger = new_logger(config.get_or_default("LOG_LEVEL", "INFO"))
        self.metrics = Registry(
            # cardinality guard: overflow increments
            # gofr_tpu_metrics_dropped_series_total{metric} instead of
            # growing the scrape unboundedly under scanner traffic
            max_series=int(
                config.get_or_default("METRICS_MAX_SERIES", "1000")
            ),
            # histogram observations self-correlate: OpenMetrics bucket
            # exemplars carry the active trace_id/dispatch_id
            exemplar_provider=(
                exemplar_provider
                if config.get_or_default("METRICS_EXEMPLARS", "on") != "off"
                else None
            ),
        )
        # bounded per-tenant usage metering (space-saving sketch behind
        # /admin/tenants): exact for the top-K heavy hitters, aggregated
        # into ~other beyond — NEVER a per-tenant Prometheus series
        self.tenants = TenantLedger(
            size=int(config.get_or_default("TENANT_LEDGER_SIZE", "256")),
            metrics=self.metrics,
        )
        # request flight recorder: per-request inference telemetry backing
        # /admin/requests and /admin/slo plus the wide-event request log
        self.telemetry = FlightRecorder(
            capacity=int(config.get_or_default("FLIGHT_RECORDER_SIZE", "512")),
            keep=int(config.get_or_default("FLIGHT_RECORDER_KEEP", "128")),
            slow_threshold_s=float(
                config.get_or_default("FLIGHT_SLOW_MS", "2000")
            ) / 1000.0,
            logger=self.logger,
            tenants=self.tenants,
        )
        # telemetry timebase: the metric history ring behind
        # /admin/timeseries and /admin/overview (and the trend data every
        # postmortem bundle carries)
        self.timebase = TimebaseSampler(
            self.metrics,
            interval_s=float(
                config.get_or_default("TIMEBASE_INTERVAL_S", "5")
            ),
            window_s=float(
                config.get_or_default("TIMEBASE_WINDOW_S", "900")
            ),
            logger=self.logger,
            start=config.get_or_default("TIMEBASE_ENABLED", "on") != "off",
        )
        # postmortem black box: wedge/crash/manual flight-data bundles
        # (the engine listener attaches in _wire_tpu once a device exists)
        self.postmortem = PostmortemStore(
            self,
            directory=config.get_or_default("POSTMORTEM_DIR", "./postmortems"),
            keep=int(config.get_or_default("POSTMORTEM_KEEP", "20")),
            min_interval_s=float(
                config.get_or_default("POSTMORTEM_MIN_INTERVAL_S", "30")
            ),
            snapshots=int(
                config.get_or_default("POSTMORTEM_SNAPSHOTS", "60")
            ),
            logger=self.logger,
        )
        if config.get("POSTMORTEM_DIR"):
            # crash + fatal-signal hooks are process-global: armed only on
            # the operator's explicit POSTMORTEM_DIR opt-in (wedge and
            # manual bundles work either way)
            self.postmortem.install_crash_hooks()
        self.services: dict[str, Any] = {}
        self.redis: Optional[Any] = None
        self.db: Optional[Any] = None
        self.tpu: Optional[Any] = None
        # the fleet front door, when this process is a router
        # (gofr_tpu.fleet.wire_fleet sets it): readiness reads its
        # draining flag, App.shutdown drains it before stopping servers
        self.fleet: Optional[Any] = None
        self._handler_pool: Optional[Any] = None
        if wire:
            self._wire_redis()
            self._wire_sql()
            self._wire_tpu()
        # SLO engine: error budgets + multi-window burn-rate alerting over
        # the flight recorder and the timebase's shed counters; its
        # verdicts land in the ring behind /admin/anomalies. A
        # malformed SLO_TARGETS fails the boot with the clause named — an
        # objective silently not alerting is the one failure mode this
        # layer must not have.
        self.slo: Optional[SloEngine] = None
        if config.get_or_default("SLO", "on") != "off":
            ring_size = int(config.get_or_default("ANOMALY_RING_SIZE", "256"))
            if ring_size < 1:
                raise ValueError("ANOMALY_RING_SIZE must be >= 1")
            self.slo = SloEngine(
                self.telemetry,
                timebase=self.timebase,
                metrics=self.metrics,
                logger=self.logger,
                targets=config.get_or_default("SLO_TARGETS", DEFAULT_TARGETS),
                ring=AnomalyRing(ring_size),
                fast_s=float(config.get_or_default("SLO_BURN_FAST_S", "300")),
                fast_long_s=float(
                    config.get_or_default("SLO_BURN_FAST_LONG_S", "3600")
                ),
                slow_s=float(
                    config.get_or_default("SLO_BURN_SLOW_S", "21600")
                ),
                slow_long_s=float(
                    config.get_or_default("SLO_BURN_SLOW_LONG_S", "259200")
                ),
                fast_rate=float(
                    config.get_or_default("SLO_BURN_FAST_RATE", "14.4")
                ),
                slow_rate=float(
                    config.get_or_default("SLO_BURN_SLOW_RATE", "6")
                ),
                interval_s=float(
                    config.get_or_default("SLO_EVAL_INTERVAL_S", "15")
                ),
                start=True,
            )

    # -- conditional wiring (parity: container.go:48-86) ---------------------
    def _wire_redis(self) -> None:
        host = self.config.get("REDIS_HOST")
        if not host:
            return
        port = int(self.config.get_or_default("REDIS_PORT", "6379"))
        try:
            from gofr_tpu.datasource.redis import new_client

            self.redis = new_client(host, port, self.logger)
            self.logger.infof("connected to redis at %s:%s", host, port)
        except Exception as exc:  # non-fatal degraded startup
            self.logger.errorf("could not connect to redis at %s:%s, error: %s", host, port, exc)
            self.redis = None

    def _wire_sql(self) -> None:
        name = self.config.get("DB_NAME")
        host = self.config.get("DB_HOST")
        if not name and not host:
            return
        try:
            from gofr_tpu.datasource.sql import new_sql

            self.db = new_sql(self.config, self.logger)
            self.logger.infof("connected to database '%s'", name or host)
        except Exception as exc:
            self.logger.errorf("could not connect to database, error: %s", exc)
            self.db = None

    def _wire_tpu(self) -> None:
        enabled = (self.config.get_or_default("TPU_ENABLED", "") or "").lower()
        model = self.config.get("MODEL_NAME")
        if enabled not in ("true", "1", "yes") and not model:
            return
        try:
            from gofr_tpu.tpu import new_device

            # the multi-host join happens inside the device BOOT path
            # (before its device probe): jax.distributed.initialize blocks
            # until peers arrive, and blocking container wiring would hang
            # the server before it listens — the exact failure
            # TPU_BOOT=background exists to avoid
            self.tpu = new_device(self.config, self.logger, self.metrics)
            # a wedged or boot-failed engine writes its own black-box
            # bundle the moment the state machine says so
            self.postmortem.watch_engine(self.tpu.engine)
            # the recovery supervisor writes its bundle SYNCHRONOUSLY
            # before quarantining the stuck dispatch (the quarantine
            # destroys the live watchdog evidence a bundle must carry;
            # rate limiting dedupes against the listener's own write)
            self.tpu.recovery.postmortem = (
                lambda detail: self.postmortem.write(
                    reason="wedged", detail=detail
                )
            )
            if self.config.get_or_default("TPU_BOOT", "") == "background":
                # the device logs its describe() line once probe+warmup end
                self.logger.infof(
                    "TPU datasource booting in background (model=%s); "
                    "readiness at /.well-known/ready",
                    self.config.get("MODEL_NAME"),
                )
            else:
                self.logger.infof("TPU datasource ready: %s", self.tpu.describe())
        except Exception as exc:
            self.logger.errorf("could not initialize TPU datasource, error: %s", exc)
            self.tpu = None

    # -- health (parity: container.go:26-38) ---------------------------------
    def health(self) -> dict[str, Any]:
        details: dict[str, Any] = {}
        overall = UP
        for name, source in (("redis", self.redis), ("sql", self.db), ("tpu", self.tpu)):
            if source is None:
                continue
            try:
                h: Health = source.health_check()
            except Exception as exc:
                h = Health(DOWN, {"error": str(exc)})
            details[name] = h.to_dict()
            if h.status != UP:
                overall = DOWN
        # NOTE: registered service clients are NOT probed here (parity:
        # container.go:26-38 checks only datasources). Probing downstreams
        # from the health endpoint recurses when a service points at this
        # same app (the reference example does exactly that).
        return {"status": overall, "details": details}

    def get_http_service(self, name: str) -> Any:
        """Parity: container.go:93 — nil-safe lookup."""
        return self.services.get(name)

    @property
    def handler_executor(self) -> Any:
        """Dedicated thread pool for SYNC handlers (HANDLER_THREADS,
        default 64). asyncio's default executor is sized cpu_count+4 —
        five threads on a 1-CPU serving VM — and sync handlers BLOCK (a
        token generation holds its thread for seconds), so the default
        silently caps concurrent requests at the executor size: measured
        8 decode streams collapsing to 5 concurrent + 3 queued for
        seconds. Blocking handlers need I/O-sized pools, not CPU-sized."""
        if self._handler_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            workers = int(self.config.get_or_default("HANDLER_THREADS", "64"))
            self._handler_pool = ThreadPoolExecutor(
                max_workers=workers, thread_name_prefix="gofr-handler"
            )
        return self._handler_pool

    def close(self) -> None:
        if self.slo is not None:
            self.slo.close()  # stops the gofr-slo evaluator thread
        if self.fleet is not None:
            try:
                self.fleet.close()  # stops the health-prober thread
            except Exception:
                pass
        for source in (self.redis, self.db, self.tpu):
            closer = getattr(source, "close", None)
            if closer:
                try:
                    closer()
                except Exception:
                    pass
        self.timebase.close()
        self.postmortem.detach()
        if self._handler_pool is not None:
            self._handler_pool.shutdown(wait=False)


def new_container(config: Config) -> Container:
    """Parity: container/container.go:40."""
    return Container(config)
