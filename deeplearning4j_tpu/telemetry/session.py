"""TrainTelemetry — the host side of the in-graph metrics loop.

A metrics-threaded train step returns a pytree of DEVICE scalars each step.
Fetching them eagerly would add a device→host sync per step, which stalls
the dispatch queue (its cost on a local chip: not measured); TrainTelemetry
instead buffers the device references and fetches the whole window in ONE
``jax.device_get`` every ``interval`` steps, then fans the values out to:

- the JSONL step-event log (telemetry/step_log.py) — ts, step, wall_ms,
  tokens/s, every metric;
- the MetricsRegistry — gauges (loss/grad_norm/param_norm/update_ratio,
  per-expert ``router_load{expert=...}``), the ``train_steps_total``
  counter, and the ``train_step_ms`` histogram — which the UI serves at
  ``/metrics`` (Prometheus) and ``/api/telemetry`` (JSON).

``static`` metadata (mesh axes, attention impl, model dims) is stamped on
every log line and exported as a ``<prefix>_run_info`` info-gauge.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax

from deeplearning4j_tpu.telemetry.registry import MetricsRegistry
from deeplearning4j_tpu.telemetry.step_log import StepLogWriter

DEFAULT_INTERVAL = 10


class TrainTelemetry:
    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 step_log_path: Optional[str] = None,
                 interval: int = DEFAULT_INTERVAL,
                 tokens_per_step: Optional[int] = None,
                 static: Optional[Dict] = None,
                 prefix: str = "train"):
        self.registry = registry if registry is not None else MetricsRegistry()
        self.interval = max(1, int(interval))
        self.tokens_per_step = tokens_per_step
        self.prefix = prefix
        self.static = dict(static or {})
        self._writer = (StepLogWriter(step_log_path, static=self.static)
                        if step_log_path else None)
        self._buf = []  # (step, wall_ms, device-metrics) — no host sync
        self._last_t: Optional[float] = None
        self.steps_recorded = 0
        self.records = []  # fetched records (host values), for callers
        if self.static:
            self.registry.gauge(
                f"{prefix}_run_info",
                labels={k: str(v) for k, v in self.static.items()}).set(1)

    # ---- hot path ----
    def record(self, step: int, metrics) -> None:
        """Buffer one step's device metrics; syncs only at interval edges."""
        now = time.perf_counter()
        wall_ms = (None if self._last_t is None
                   else (now - self._last_t) * 1000.0)
        self._last_t = now
        self._buf.append((step, wall_ms, metrics))
        self.steps_recorded += 1
        if len(self._buf) >= self.interval:
            self.flush()

    # ---- the one device->host sync per window ----
    def flush(self) -> None:
        if not self._buf:
            return
        fetched = jax.device_get([m for _, _, m in self._buf])
        buf, self._buf = self._buf, []
        for (step, wall_ms, _), vals in zip(buf, fetched):
            host = {k: (v.tolist() if hasattr(v, "tolist") else v)
                    for k, v in vals.items()}
            tps = None
            if wall_ms and self.tokens_per_step:
                tps = self.tokens_per_step / (wall_ms / 1000.0)
            self._export(step, wall_ms, tps, host)

    def _export(self, step, wall_ms, tps, host: Dict) -> None:
        reg, p = self.registry, self.prefix
        reg.counter(f"{p}_steps_total").inc()
        reg.gauge(f"{p}_step").set(step)
        for k, v in host.items():
            if isinstance(v, (list, tuple)):
                for i, vi in enumerate(v):
                    reg.gauge(f"{p}_{k}", labels={"expert": str(i)}
                              if k == "router_load" else
                              {"index": str(i)}).set(float(vi))
            elif isinstance(v, (int, float)):
                reg.gauge(f"{p}_{k}").set(float(v))
        if wall_ms is not None:
            reg.histogram(f"{p}_step_ms").observe(wall_ms)
        if tps is not None:
            reg.gauge(f"{p}_tokens_per_sec").set(tps)
        rec = None
        if self._writer:
            rec = self._writer.write(step, wall_ms=wall_ms,
                                     tokens_per_sec=tps, **host)
        if rec is None:
            rec = {"step": step, "wall_ms": wall_ms,
                   "tokens_per_sec": tps, **host}
        self.records.append(rec)

    def close(self) -> None:
        self.flush()
        if self._writer:
            self._writer.close()

    def __enter__(self) -> "TrainTelemetry":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
