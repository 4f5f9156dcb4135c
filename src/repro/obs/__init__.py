"""Observability for the SAFL engines: tracing, metrics, profiling.

- :mod:`repro.obs.trace` — per-upload lifecycle + per-horizon span
  tracer on the simulated clock (JSONL; identical streams on both
  engine paths).
- :mod:`repro.obs.export` — Chrome-trace/Perfetto export, schema
  validation, and JSON-native conversion (``to_native``).
- :mod:`repro.obs.metrics` — counters/gauges/histograms registry with
  Prometheus-text and JSON exposition; ``from_engine`` snapshots.
- :mod:`repro.obs.profile` — jit compile-count tracking
  (``CompileLog``), host-transfer counting (``TransferScope``), and an
  optional ``jax.profiler`` toggle.
- stage spans — ``FLEngine.run`` writes ``safl.*`` wall-clock spans
  (``jax.profiler.TraceAnnotation``) into the profiler's trace, per
  stage, joined to the simulated-clock tracer by the round index.
- :mod:`repro.obs.report` — ``python -m repro.obs.report`` ASCII
  timeline CLI.

Enable via ``FLConfig.trace_level``/``trace_dir`` or ``fl_sim
--trace-dir``.  See ``obs/README.md`` for the Perfetto workflow.
"""
# NOTE: repro.obs.report is deliberately NOT imported here — it is the
# ``python -m repro.obs.report`` entry point, and importing it from the
# package __init__ would trip runpy's double-import warning.
from repro.obs import export, metrics, profile, trace  # noqa: F401
from repro.obs.export import export_chrome_trace, to_native  # noqa: F401
from repro.obs.metrics import MetricsRegistry, from_engine  # noqa: F401
from repro.obs.profile import (CompileLog, TransferScope,  # noqa: F401
                               engine_compile_log, record_transfer)
from repro.obs.trace import SpanTracer, canonical  # noqa: F401
