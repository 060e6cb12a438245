"""Unified telemetry: typed event records, phase spans, run analysis.

``events``  — versioned record schemas + the JSONL ``Recorder`` (owns the
              run-scoped comm-counter context).
``spans``   — the training loop's host spans on the profiler's trace
              (``train.*``), the straggler watchdog, profile-mode
              samplers.
``report``  — breakdown / A-vs-B diff / validation CLI core
              (``scripts/obs_report.py``).
"""
from repro.obs.events import (SCHEMA_VERSION, SCHEMAS, Recorder, SchemaError,
                              infer_event, step_fields, validate_record)
from repro.obs.spans import (SpanTracker, StragglerWatchdog,
                             compiled_fn_costs, device_bytes_in_use,
                             hlo_costs, live_buffer_mb)

__all__ = [
    'SCHEMA_VERSION', 'SCHEMAS', 'Recorder', 'SchemaError', 'infer_event',
    'step_fields', 'validate_record',
    'SpanTracker', 'StragglerWatchdog', 'compiled_fn_costs',
    'device_bytes_in_use', 'hlo_costs', 'live_buffer_mb',
]
