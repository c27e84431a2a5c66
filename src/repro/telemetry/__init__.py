"""Round-level telemetry: in-band metrics, phase spans, and the structured
event stream of every run.

* :class:`TelemetrySpec` — the declarative policy on
  ``Experiment.telemetry`` (metric groups + sink path);
* :class:`EventLog` — the schema-versioned, append-only JSONL event
  writer every driver emits to (``launch.train``, ``launch.dryrun``,
  ``benchmarks.run``);
* :func:`phase` / :class:`SpanTotals` / :func:`annotate` — host
  wall-clock spans (wrapping ``jax.profiler.TraceAnnotation``), summed in
  memory for a loop's per-step phases, and in-jit named scopes;
* :func:`comm_plan` / :func:`round_bytes` — the per-round analytic
  communication-bytes model ``comm`` events carry;
* ``python -m repro.telemetry.validate`` — schema validation + comm-bytes
  reconciliation; ``python -m repro.launch.metrics`` — the summarizer.

The in-band metrics themselves are computed by the fused engine
(``repro.optim.sequences.make_engine(..., telemetry=)``) as a side output
of the jitted step — with the layer absent the side output stays exactly
``{"step": ...}`` and every trajectory, jit cache key and checkpoint
structure is bit-identical to a telemetry-free build.
"""
from repro.telemetry.comm import CommPlan, comm_plan, round_bytes
from repro.telemetry.events import (EVENT_SCHEMA_VERSION, REQUIRED_KEYS,
                                    EventLog, TelemetryError, read_events)
from repro.telemetry.spec import (METRIC_GROUPS, TelemetrySpec,
                                  resolve_metric_groups)
from repro.telemetry.trace import SpanTotals, annotate, measure_run, phase
from repro.telemetry.validate import validate_events

__all__ = [
    "CommPlan", "EVENT_SCHEMA_VERSION", "EventLog", "METRIC_GROUPS",
    "REQUIRED_KEYS", "SpanTotals", "TelemetryError", "TelemetrySpec",
    "annotate", "comm_plan", "measure_run", "phase", "read_events",
    "resolve_metric_groups", "round_bytes", "validate_events",
]
