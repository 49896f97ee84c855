"""`rocm_apex_tpu.monitor` — training/serving observability, five pillars.

The reference scattered its telemetry (nvmarker payloads in pyprof,
`_timers.py` synchronized timers, the amp scaler's overflow counter);
this package is the shared layer the ROADMAP's production story needs:

* **in-graph metrics** (`metrics.py`): the jit-safe `Metrics` pytree a
  train step threads through and returns — grad norms, update ratios,
  loss scale, activation RMS taps — zero extra traces, shard_map-
  correct psums;
* **host pipeline** (`logger.py`): `MetricsLogger` with windowed
  aggregation, `Timers`-sync step timing, tokens/sec + MFU from the
  shared `model_flops` accounting (`flops.py`), device-memory stats,
  and pluggable writers (`JsonlWriter`, `TensorBoardWriter`);
* **static auditor** (`audit.py`): walk a `ClosedJaxpr` and report
  collective counts/bytes and dot FLOPs — the executable form of the
  PR-3 "no gathered activation / ring collectives" invariants, and
  bench.py's ``--audit`` report;
* **graph-contract linter** (`lint.py`): declarative rules checked
  against traced programs — precision policy, materialization
  budgets, collective contracts, donation, trace stability — the
  policy layer over the auditor's accounting; `tools/graphlint.py`
  diffs a registry of named configs against the checked-in
  `tools/graph_contracts.json` manifest (CI gate), and bench.py grows
  a ``--lint`` flag;
* **span tracer** (`trace.py`): host-side wall-clock spans in a
  thread-safe ring buffer, exported as Perfetto-loadable Chrome trace
  JSON and aligned with device captures via
  `jax.profiler.TraceAnnotation` — the serving engine's per-request
  timelines and the train loop's step spans ride it. Fleet-causal on
  top: the router mints a `trace_id` per admitted request that rides
  every hop, `merge_traces` folds N replica tracers + the router
  tracer into ONE Perfetto JSON (per-replica process ids), and
  `RetraceSentinel` subscribes to jax's compilation events to turn
  "the trace count stays 1" into a runtime gate
  (``retrace_policy="raise"``);
* **flight recorder** (`recorder.py`): last-k step snapshots plus
  in-graph per-param-group nonfinite probes; on a NaN/Inf anomaly it
  dumps a jsonl bundle naming the offending group — a mid-run NaN
  becomes a diagnosable artifact instead of a dead run;
* **telemetry plane** (`telemetry.py` / `slo.py` / `exporter.py`):
  the production export surface — a mergeable constant-memory metric
  registry (`Counter`/`Gauge`/`Histogram` with log-spaced buckets:
  bucket-wise merge reproduces combined-stream percentiles, the
  multi-replica prerequisite), declarative `SLO` objectives with
  Google-SRE multi-window burn-rate alerts (`SLOMonitor`), and a
  stdlib-only HTTP exporter (`TelemetryServer`) serving ``/metrics``
  (Prometheus text), ``/healthz`` (engine watchdog/drain liveness),
  and ``/varz`` (JSON incl. device-memory watermarks). The serving
  engine's ``stats()`` rides the registry; `RegistryWriter` joins
  training runs to the same plane; disabled registries follow the
  `NULL_TRACER` zero-overhead idiom (`NULL_REGISTRY`). The
  time-series sensor plane (`timeseries.py`) rides the same registry:
  `TimeSeriesStore` keeps a fixed-memory ring of periodic
  ``snapshot()`` samples and answers the windowed
  `rate`/`delta`/`quantile_over` queries the elastic-fleet
  controller's sensors need, served at ``/timeseries``.

See docs/observability.md for the full tour; `rocm_apex_tpu.profiler`
remains the trace-capture layer (device timelines), while this package
owns the per-step scalar stream, wall-clock spans, and static program
accounting.
"""

from rocm_apex_tpu.monitor.audit import (
    AuditReport,
    assert_no_intermediate,
    audit,
    audit_jaxpr,
)
from rocm_apex_tpu.monitor.flops import (
    UnknownDeviceError,
    chip_peaks,
    mfu,
    model_flops,
    peak_flops_per_chip,
    resnet50_train_flops,
    transformer_train_flops,
)
from rocm_apex_tpu.monitor.exporter import (
    TelemetryServer,
    engine_health,
    fleet_health,
    start_exporter,
)
from rocm_apex_tpu.monitor.logger import (
    JsonlWriter,
    MetricsLogger,
    RegistryWriter,
    TensorBoardWriter,
    device_memory_stats,
)
from rocm_apex_tpu.monitor.lint import (
    CollectiveContract,
    DonationContract,
    LintReport,
    LintSubject,
    NoMaterialization,
    PrecisionPolicy,
    TraceStability,
    Violation,
    run_lint,
    walk_eqns,
)
from rocm_apex_tpu.monitor.metrics import Metrics, activation_stats, tree_norm
from rocm_apex_tpu.monitor.recorder import FlightRecorder, group_nonfinite
from rocm_apex_tpu.monitor.slo import (
    DEFAULT_BURN_RULES,
    BurnRule,
    SLO,
    SLOMonitor,
    TenantSLOBoard,
)
from rocm_apex_tpu.monitor.telemetry import (
    DEFAULT_REGISTRY,
    NULL_REGISTRY,
    CardinalityError,
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    log_buckets,
)
from rocm_apex_tpu.monitor.timeseries import TimeSeriesStore
from rocm_apex_tpu.monitor.trace import (
    COMPILE_EVENT_PHASES,
    NULL_TRACER,
    RetraceError,
    RetraceSentinel,
    Tracer,
    export_merged_trace,
    merge_traces,
    mint_trace_id,
    trace_lifelines,
)

__all__ = [
    "Metrics",
    "tree_norm",
    "activation_stats",
    "MetricsLogger",
    "JsonlWriter",
    "TensorBoardWriter",
    "device_memory_stats",
    "model_flops",
    "transformer_train_flops",
    "resnet50_train_flops",
    "peak_flops_per_chip",
    "chip_peaks",
    "UnknownDeviceError",
    "mfu",
    "AuditReport",
    "audit",
    "audit_jaxpr",
    "assert_no_intermediate",
    "Violation",
    "LintReport",
    "LintSubject",
    "run_lint",
    "walk_eqns",
    "PrecisionPolicy",
    "NoMaterialization",
    "CollectiveContract",
    "DonationContract",
    "TraceStability",
    "Tracer",
    "NULL_TRACER",
    "mint_trace_id",
    "merge_traces",
    "export_merged_trace",
    "trace_lifelines",
    "RetraceSentinel",
    "RetraceError",
    "COMPILE_EVENT_PHASES",
    "TimeSeriesStore",
    "FlightRecorder",
    "group_nonfinite",
    "MetricRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "CardinalityError",
    "log_buckets",
    "DEFAULT_REGISTRY",
    "NULL_REGISTRY",
    "RegistryWriter",
    "SLO",
    "SLOMonitor",
    "TenantSLOBoard",
    "BurnRule",
    "DEFAULT_BURN_RULES",
    "TelemetryServer",
    "engine_health",
    "fleet_health",
    "start_exporter",
]
