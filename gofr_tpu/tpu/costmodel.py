"""Dispatch cost model + residual watchtower (ROADMAP item 5 substrate).

Before this module the engine had no compiled-cost truth: per-dispatch
MFU/MBU came from the ``2·N·tokens`` floor in ``tpu/flops.py``, nothing
predicted how long a dispatch *should* take, and a dispatch running 10x
slower than its shape warrants was invisible until the watchdog's blunt
timeout. Three layers fix that:

- **CostSheet** — per-(kind, bucket, batch, verify-width) compiled cost:
  flops / bytes-accessed / peak-memory harvested from each executable's
  ``compiled.cost_analysis()`` / ``memory_analysis()`` at warmup (source
  ``hlo``), or a synthetic entry for the echo runner (source
  ``synthetic``) so the whole predict→observe→alert path runs
  compile-free in tier-1.
- **Roofline prediction** — ``max(flops/eff_flops, bytes/eff_bw) +
  overhead_ms`` with per-device-kind *effective* (calibrated, not
  nominal) coefficients loaded from a committed cost-profile JSON
  (``cost_profile.json`` next to this module; ``tools/costcal.py`` fits
  the coefficients from dispatch-timeline records and ``--check``s the
  committed fit in CI). Every ``DispatchRecord`` is annotated at
  ``begin`` with ``predicted_ms`` and at ``finish`` with
  ``residual_ratio`` (observed/predicted) by the
  :class:`~gofr_tpu.tpu.introspect.DispatchTimeline` hooks.
- **Anomaly engine** — per-family (kind, bucket) residual EMAs feed the
  ``gofr_tpu_dispatch_residual_ratio{kind,bucket}`` gauge; a dispatch
  exceeding ``COSTMODEL_ANOMALY_FACTOR``× its prediction (cause
  ``slow_dispatch``), or a family EMA drifting past
  ``COSTMODEL_EMA_BAND`` (cause ``ema_drift``, latched per family until
  it re-enters the band), lands a typed event in an
  ``ANOMALY_RING_SIZE`` ring served by ``GET /admin/anomalies``, counted
  on ``gofr_tpu_dispatch_anomalies_total{kind,cause}``, snapshotted into
  postmortem bundles, and surfaced per-replica on
  ``/admin/fleet/overview``.

False-positive floor: every anomaly verdict additionally requires the
absolute excess (observed − predicted) to clear
``COSTMODEL_MIN_ANOMALY_MS`` — a microsecond echo dispatch with a noisy
ratio must never page anyone, and a healthy run produces ZERO anomalies
(the tier-1 e2e asserts exactly that).

Host-side only: prediction and residual accounting are a dict lookup and
a handful of float ops per dispatch (bench.py's costmodel_microbench
keeps that honest); nothing here touches a device.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Optional

# the cause vocabulary and the evidence ring live in gofr_tpu/anomaly.py
# (host-side, jax-import-free — the SLO engine shares both on processes
# that never wire a device); re-exported here so every existing
# ``from gofr_tpu.tpu.costmodel import AnomalyRing`` keeps working
from gofr_tpu.anomaly import ANOMALY_CAUSES, AnomalyRing

__all__ = [
    "ANOMALY_CAUSES", "AnomalyRing", "CostModel", "CostSheet",
    "UNPRICED_KINDS",
]

# dispatch kinds that never get a prediction: boot-time work has no
# steady-state cost truth (a warmup compile's duration IS the compile)
UNPRICED_KINDS = ("warmup_compile", "device_probe")

# committed per-device-kind roofline coefficients (tools/costcal.py owns
# the fit; CI --checks that the committed numbers reproduce)
DEFAULT_PROFILE_PATH = os.path.join(os.path.dirname(__file__), "cost_profile.json")

# a family EMA is meaningless over a couple of samples — drift verdicts
# wait for this many observed dispatches per (kind, bucket) family
EMA_MIN_SAMPLES = 8

# when no profile row matches the probed device kind, predictions fall
# back to this fraction of the NOMINAL peak (flops.py tables) — labeled
# "nominal" in the calibration provenance so an uncalibrated replica is
# visible on /admin/costmodel, not silently trusted
NOMINAL_EFFICIENCY = 0.5


class CostSheet:
    """One executable family's compiled cost (immutable after install)."""

    __slots__ = (
        "kind", "bucket", "batch", "width", "flops", "bytes_accessed",
        "peak_memory_bytes", "base_ms", "source",
    )

    def __init__(
        self,
        kind: str,
        bucket: int = 0,
        batch: int = 0,
        width: int = 0,
        flops: float = 0.0,
        bytes_accessed: float = 0.0,
        peak_memory_bytes: int = 0,
        base_ms: Optional[float] = None,
        source: str = "hlo",
    ):
        self.kind = kind
        self.bucket = int(bucket)
        self.batch = int(batch)
        self.width = int(width)
        self.flops = float(flops or 0.0)
        self.bytes_accessed = float(bytes_accessed or 0.0)
        self.peak_memory_bytes = int(peak_memory_bytes or 0)
        # synthetic sheets (echo) carry a direct per-dispatch cost in ms
        # instead of flops/bytes — the roofline terms don't apply
        self.base_ms = base_ms
        self.source = source  # "hlo" | "synthetic"

    def key(self) -> tuple:
        return (self.kind, self.bucket, self.batch, self.width)

    def to_dict(self) -> dict[str, Any]:
        return {
            "kind": self.kind,
            "bucket": self.bucket or None,
            "batch": self.batch or None,
            "width": self.width or None,
            "flops": self.flops or None,
            "bytes_accessed": self.bytes_accessed or None,
            "peak_memory_bytes": self.peak_memory_bytes or None,
            "base_ms": self.base_ms,
            "source": self.source,
        }


class CostModel:
    """Cost sheets + calibrated roofline prediction + residual/anomaly
    accounting. Wired into :class:`DispatchTimeline` as the single
    predict→observe chokepoint: ``annotate(record)`` at ``begin``,
    ``observe(record)`` at ``finish`` — one integration point covers the
    batcher, chunked prefill, the decode pool, and spec verifies."""

    def __init__(
        self,
        metrics: Any = None,
        logger: Any = None,
        profile_path: Optional[str] = None,
        anomaly_factor: float = 4.0,
        min_anomaly_ms: float = 50.0,
        ema_alpha: float = 0.2,
        ema_band: float = 2.5,
        ring_size: int = 256,
    ):
        if anomaly_factor <= 1.0:
            raise ValueError("COSTMODEL_ANOMALY_FACTOR must be > 1")
        if min_anomaly_ms < 0:
            raise ValueError("COSTMODEL_MIN_ANOMALY_MS must be >= 0")
        if not (0.0 < ema_alpha <= 1.0):
            raise ValueError("COSTMODEL_EMA_ALPHA must be in (0, 1]")
        if ema_band <= 1.0:
            raise ValueError("COSTMODEL_EMA_BAND must be > 1")
        self.logger = logger
        self.anomaly_factor = float(anomaly_factor)
        self.min_anomaly_ms = float(min_anomaly_ms)
        self.ema_alpha = float(ema_alpha)
        self.ema_band = float(ema_band)
        self.ring = AnomalyRing(ring_size)
        self._lock = threading.Lock()
        # sheets: exact key -> sheet, plus two fallback indexes — the
        # compiled shape (bucket x padded batch) determines the cost, so
        # a record whose batch_size is below the padded warm batch still
        # resolves to its bucket's sheet; kind-wide wildcards are how the
        # echo runner's synthetic table covers every echo dispatch
        self._sheets: dict[tuple, CostSheet] = {}
        self._by_bucket: dict[tuple, CostSheet] = {}
        self._wildcard: dict[str, CostSheet] = {}
        # residual families: (kind, bucket) -> EMA state
        self._families: dict[tuple, dict[str, Any]] = {}
        # calibration: profile rows + the resolved coefficients
        self._profile_path = profile_path or DEFAULT_PROFILE_PATH
        self._profile_rows: dict[str, dict[str, Any]] = {}
        self._profile_meta: dict[str, Any] = {}
        self._load_profile()
        self.eff_flops: Optional[float] = None
        self.eff_bw: Optional[float] = None
        self.overhead_ms: float = 0.0
        self.calibration: dict[str, Any] = {"source": "uncalibrated"}
        if metrics is not None:
            self._residual_gauge = metrics.gauge(
                "gofr_tpu_dispatch_residual_ratio",
                "per-family EMA of observed/predicted dispatch latency "
                "(1.0 = the calibrated roofline holds; the anomaly band "
                "is COSTMODEL_EMA_BAND)",
                labels=("kind", "bucket"),
            )
            self._anomaly_counter = metrics.counter(
                "gofr_tpu_dispatch_anomalies_total",
                "dispatch cost-model anomalies by kind and cause "
                "(slow_dispatch, ema_drift)",
                labels=("kind", "cause"),
            )
        else:
            self._residual_gauge = self._anomaly_counter = None

    # -- calibration ----------------------------------------------------------
    def _load_profile(self) -> None:
        """Load the committed cost-profile JSON. A missing or corrupt
        profile leaves the rows empty (calibration then resolves to the
        labeled ``nominal`` fallback) — never a boot failure."""
        try:
            with open(self._profile_path, "r", encoding="utf-8") as fh:
                profile = json.load(fh)
            rows = profile.get("device_kinds") or {}
            if not isinstance(rows, dict):
                raise ValueError("device_kinds must be an object")
            self._profile_rows = {
                str(k).lower(): dict(v) for k, v in rows.items()
            }
            self._profile_meta = {
                k: v for k, v in profile.items() if k != "device_kinds"
            }
        except FileNotFoundError:
            self._profile_rows = {}
            self._profile_meta = {"error": f"missing: {self._profile_path}"}
        except Exception as exc:
            self._profile_rows = {}
            self._profile_meta = {"error": f"unreadable: {exc!r}"}
            if self.logger is not None:
                self.logger.warnf(
                    "costmodel: cost profile %s unreadable (%r) — "
                    "predictions fall back to nominal coefficients",
                    self._profile_path, exc,
                )

    def calibrate(self, device_kind: str, platform: str) -> None:
        """Resolve roofline coefficients for the probed device kind:
        ordered substring match over the committed profile rows (the
        flops.py table discipline), else ``NOMINAL_EFFICIENCY`` x the
        nominal peaks — labeled so /admin/costmodel shows whether this
        replica predicts from a real fit or a guess."""
        kind = (device_kind or "").lower()
        row = None
        matched = None
        for needle, candidate in self._profile_rows.items():
            if needle in kind or needle == platform:
                row = candidate
                matched = needle
                break
        if row is not None:
            eff_flops = float(row.get("eff_flops") or 0.0)
            eff_bw = float(row.get("eff_bw") or 0.0)
            overhead = float(row.get("overhead_ms") or 0.0)
            source = "profile"
        else:
            from gofr_tpu.tpu.flops import device_peak_flops, device_peak_hbm_bw

            eff_flops = device_peak_flops(device_kind, platform) * NOMINAL_EFFICIENCY
            eff_bw = device_peak_hbm_bw(device_kind, platform) * NOMINAL_EFFICIENCY
            overhead = 0.2
            source = "nominal"
        with self._lock:
            self.eff_flops = eff_flops if eff_flops > 0 else None
            self.eff_bw = eff_bw if eff_bw > 0 else None
            self.overhead_ms = overhead
            self.calibration = {
                "source": source,
                "matched": matched,
                "device_kind": str(device_kind),
                "platform": platform,
                "eff_flops": eff_flops,
                "eff_bw": eff_bw,
                "overhead_ms": overhead,
                "profile_path": self._profile_path,
                "profile": dict(self._profile_meta),
            }

    # -- sheet install / lookup ----------------------------------------------
    def install(self, sheet: CostSheet) -> None:
        with self._lock:
            self._sheets[sheet.key()] = sheet
            if sheet.bucket or sheet.batch or sheet.width:
                self._by_bucket[(sheet.kind, sheet.bucket)] = sheet
            else:
                self._wildcard[sheet.kind] = sheet

    def install_synthetic(self, kind: str, base_ms: float) -> None:
        """Kind-wide synthetic sheet (echo runner): one dispatch of
        ``kind`` costs ``base_ms`` regardless of bucket/batch — the
        compile-free cost truth tier-1 drives the whole loop with."""
        self.install(CostSheet(kind, base_ms=float(base_ms), source="synthetic"))

    def harvest(
        self, kind: str, bucket: int, batch: int, compiled: Any, width: int = 0
    ) -> Optional[CostSheet]:
        """Pull ``cost_analysis()`` / ``memory_analysis()`` off a compiled
        executable into an installed sheet. Defensive by contract: PJRT
        backends disagree about both calls (CPU returns partial dicts,
        some backends raise) — a family that yields neither flops nor
        bytes installs nothing and returns None."""
        flops = bytes_accessed = 0.0
        peak_memory = 0
        try:
            cost = compiled.cost_analysis()  # one dict on the installed jax
            flops = float(cost.get("flops") or 0.0)
            bytes_accessed = float(cost.get("bytes accessed") or 0.0)
        except Exception as exc:
            if self.logger is not None:
                self.logger.debugf(
                    "costmodel: cost_analysis unavailable for %s/%s: %r",
                    kind, bucket, exc,
                )
        try:
            mem = compiled.memory_analysis()
            peak_memory = int(
                getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
            )
        except Exception as exc:
            if self.logger is not None:
                self.logger.debugf(
                    "costmodel: memory_analysis unavailable for %s/%s: %r",
                    kind, bucket, exc,
                )
        if flops <= 0 and bytes_accessed <= 0:
            return None
        sheet = CostSheet(
            kind, bucket=bucket, batch=batch, width=width, flops=flops,
            bytes_accessed=bytes_accessed, peak_memory_bytes=peak_memory,
            source="hlo",
        )
        self.install(sheet)
        return sheet

    def sheet_for(
        self, kind: str, bucket: int = 0, batch: int = 0, width: int = 0
    ) -> Optional[CostSheet]:
        """Exact key, else the bucket's sheet (the compiled shape pads
        every batch to it), else the kind-wide wildcard (synthetic)."""
        with self._lock:
            sheet = self._sheets.get((kind, bucket, batch, width))
            if sheet is None:
                sheet = self._by_bucket.get((kind, bucket))
            if sheet is None:
                sheet = self._wildcard.get(kind)
            return sheet

    def hlo_flops(self, kind: str, bucket: int = 0, batch: int = 0) -> Optional[float]:
        """HLO-derived flops for the family, or None — the MFU upgrade
        hook (approximation stays the fallback, source labeled)."""
        sheet = self.sheet_for(kind, bucket=bucket, batch=batch)
        if sheet is not None and sheet.source == "hlo" and sheet.flops > 0:
            return sheet.flops
        return None

    def hlo_bytes(self, kind: str, bucket: int = 0, batch: int = 0) -> Optional[float]:
        """HLO-derived bytes-accessed for the family, or None — the MBU
        upgrade hook."""
        sheet = self.sheet_for(kind, bucket=bucket, batch=batch)
        if sheet is not None and sheet.source == "hlo" and sheet.bytes_accessed > 0:
            return sheet.bytes_accessed
        return None

    # -- prediction (DispatchTimeline.begin hook) -----------------------------
    def predict_ms(
        self, kind: str, bucket: int = 0, batch: int = 0, width: int = 0
    ) -> tuple[Optional[float], Optional[str]]:
        """Calibrated roofline latency for one dispatch of the family:
        ``max(flops/eff_flops, bytes/eff_bw)*1e3 + overhead_ms`` (HLO
        sheets), or ``base_ms + overhead_ms`` (synthetic). Returns
        ``(None, None)`` for unpriced kinds and families with no sheet."""
        if kind in UNPRICED_KINDS:
            return None, None
        sheet = self.sheet_for(kind, bucket=bucket, batch=batch, width=width)
        if sheet is None:
            return None, None
        if sheet.base_ms is not None:
            return sheet.base_ms + self.overhead_ms, sheet.source
        flops_s = (
            sheet.flops / self.eff_flops
            if self.eff_flops and sheet.flops > 0 else 0.0
        )
        bw_s = (
            sheet.bytes_accessed / self.eff_bw
            if self.eff_bw and sheet.bytes_accessed > 0 else 0.0
        )
        roofline = max(flops_s, bw_s)
        if roofline <= 0.0:
            return None, None
        return roofline * 1e3 + self.overhead_ms, sheet.source

    def annotate(self, record: Any) -> None:
        """``DispatchTimeline.begin`` hook: stamp the prediction (and its
        source) onto the record before the dispatch runs."""
        predicted, source = self.predict_ms(
            record.kind, bucket=record.bucket, batch=record.batch_size,
        )
        if predicted is not None:
            record.predicted_ms = predicted
            record.cost_source = source

    # -- residual / anomaly accounting (DispatchTimeline.finish hook) ---------
    def observe(self, record: Any) -> None:
        """``DispatchTimeline.finish`` hook: compute the residual, update
        the family EMA (and its gauge), and run both anomaly verdicts.
        Only clean dispatches count — an errored dispatch is a failure,
        not a latency anomaly, and would poison the EMA."""
        predicted = getattr(record, "predicted_ms", None)
        duration = record.duration
        if predicted is None or predicted <= 0 or duration is None:
            return
        if record.status != "ok":
            return
        observed_ms = duration * 1e3
        ratio = observed_ms / predicted
        record.residual_ratio = ratio
        excess_ms = observed_ms - predicted
        family = (record.kind, record.bucket)
        verdicts: list[tuple[str, float]] = []
        with self._lock:
            fam = self._families.get(family)
            if fam is None:
                fam = {
                    "ema": ratio, "ema_excess_ms": excess_ms, "n": 1,
                    "last_ratio": ratio, "drift_latched": False,
                }
                self._families[family] = fam
            else:
                a = self.ema_alpha
                fam["ema"] += a * (ratio - fam["ema"])
                fam["ema_excess_ms"] += a * (excess_ms - fam["ema_excess_ms"])
                fam["n"] += 1
                fam["last_ratio"] = ratio
            ema = fam["ema"]
            # single-dispatch verdict: factor breach AND absolute floor
            # (the floor is the no-false-positive guarantee for
            # microsecond dispatches whose ratios are pure noise)
            if ratio >= self.anomaly_factor and excess_ms >= self.min_anomaly_ms:
                verdicts.append(("slow_dispatch", self.anomaly_factor))
            # family-drift verdict: EMA past the band with a real
            # absolute excess, latched until the family re-enters the
            # band (one event per excursion, not one per dispatch)
            drifting = (
                fam["n"] >= EMA_MIN_SAMPLES
                and ema >= self.ema_band
                and fam["ema_excess_ms"] >= self.min_anomaly_ms
            )
            if drifting and not fam["drift_latched"]:
                fam["drift_latched"] = True
                verdicts.append(("ema_drift", self.ema_band))
            elif not drifting and fam["drift_latched"] and ema < self.ema_band:
                fam["drift_latched"] = False
        # metric/ring/log work OUTSIDE the family lock (lock discipline:
        # never call into another subsystem while holding it)
        if self._residual_gauge is not None:
            self._residual_gauge.set(
                ema, kind=record.kind, bucket=str(record.bucket or 0)
            )
        for cause, threshold in verdicts:
            record.anomaly = cause
            self.ring.record(
                dispatch_id=record.dispatch_id,
                kind=record.kind,
                bucket=record.bucket or 0,
                batch_size=record.batch_size or 0,
                cause=cause,
                predicted_ms=round(predicted, 4),
                observed_ms=round(observed_ms, 4),
                residual_ratio=round(ratio, 4),
                ema=round(ema, 4),
                threshold=threshold,
                source=getattr(record, "cost_source", None),
                detail=record.detail or None,
            )
            if self._anomaly_counter is not None:
                self._anomaly_counter.inc(kind=record.kind, cause=cause)
            if self.logger is not None:
                self.logger.warnf(
                    "dispatch anomaly (%s): %s bucket=%s dispatch=%d "
                    "observed=%.2fms predicted=%.2fms ratio=%.1fx",
                    cause, record.kind, record.bucket, record.dispatch_id,
                    observed_ms, predicted, ratio,
                )

    # -- read side ------------------------------------------------------------
    def residuals(self) -> dict[str, Any]:
        """Per-family residual rollup for /admin/costmodel."""
        with self._lock:
            return {
                f"{kind}/{bucket}": {
                    "ema": round(fam["ema"], 4),
                    "ema_excess_ms": round(fam["ema_excess_ms"], 4),
                    "n": fam["n"],
                    "last_ratio": round(fam["last_ratio"], 4),
                    "drift_latched": fam["drift_latched"],
                }
                for (kind, bucket), fam in sorted(self._families.items())
            }

    def sheets(self) -> list[dict[str, Any]]:
        with self._lock:
            listed = list(self._sheets.values())
        return [s.to_dict() for s in sorted(listed, key=lambda s: s.key())]

    def snapshot(self) -> dict[str, Any]:
        """The full /admin/costmodel + postmortem shape: sheets,
        calibration provenance, residual rollups, anomaly stats."""
        with self._lock:
            calibration = dict(self.calibration)
        return {
            "calibration": calibration,
            "thresholds": {
                "anomaly_factor": self.anomaly_factor,
                "min_anomaly_ms": self.min_anomaly_ms,
                "ema_alpha": self.ema_alpha,
                "ema_band": self.ema_band,
                "ema_min_samples": EMA_MIN_SAMPLES,
            },
            "sheets": self.sheets(),
            "residuals": self.residuals(),
            "anomalies": self.ring.stats(),
        }

    def overview(self) -> dict[str, Any]:
        """The small block that rides ``engine_snapshot()`` (and the
        fleet prober's /admin/engine scrape): enough to headline a
        fleet-overview row without the full sheet dump."""
        with self._lock:
            source = self.calibration.get("source")
            n_sheets = len(self._sheets)
            worst = 0.0
            for fam in self._families.values():
                if fam["n"] >= EMA_MIN_SAMPLES and fam["ema"] > worst:
                    worst = fam["ema"]
        ring = self.ring.stats()
        return {
            "calibration": source,
            "sheets": n_sheets,
            "worst_residual_ema": round(worst, 4) if worst else None,
            "anomalies_total": ring["total"],
            "last_anomaly_ts": ring["last_ts"],
        }
