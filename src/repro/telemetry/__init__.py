"""Delay-telemetry subsystem (`repro.telemetry`).

Three layers, smallest first:

- ``accumulators``: jit-compatible in-scan aggregates (delay histogram,
  tau/gamma running moments, per-window clip counters) threaded through the
  solver scans as an extra carry element.  Bitwise-neutral by contract --
  enabling telemetry never changes a solver output bit.
- ``timing``: host-side spans -- a timing event buffer the instrumented
  hot paths (program cache, bucketed/sharded runners, ``api.run``'s
  phases) write into, each span also a ``repro.*`` profiler annotation on
  the device trace's clock (the trainer's spans are annotations only).
- ``ledger``: the structured per-run ``RunRecord`` -- built by every
  ``api.run``, surfaced on ``Results.telemetry``, and appended as JSON
  lines when a ledger path is configured (``REPRO_TELEMETRY_LEDGER`` or
  ``set_ledger_path``).  ``launch/report.py`` renders ledgers;
  ``repro.analysis`` bridges (``delay_profile`` / ``clip_pressure`` /
  ``run_timeline``) consume them.
"""
from .accumulators import (TelemetryConfig, TelemetryState, DelayTelemetry,
                           init_telemetry, observe, emit_window, finalize,
                           summarize_telemetry)
from .timing import (record_timing, drain_timings, timed, span, step_span,
                     numbered_run, run_number, COMPILE_EVENT_NAMES)
from .ledger import (RunRecord, set_ledger_path, get_ledger_path,
                     append_record, read_ledger, spec_fingerprint,
                     estimate_carry_bytes, cache_delta, warn_clip_pressure,
                     LEDGER_ENV)

__all__ = [
    "TelemetryConfig", "TelemetryState", "DelayTelemetry",
    "init_telemetry", "observe", "emit_window", "finalize",
    "summarize_telemetry",
    "record_timing", "drain_timings", "timed", "span", "step_span",
    "numbered_run", "run_number", "COMPILE_EVENT_NAMES",
    "RunRecord", "set_ledger_path", "get_ledger_path", "append_record",
    "read_ledger", "spec_fingerprint", "estimate_carry_bytes",
    "cache_delta", "warn_clip_pressure", "LEDGER_ENV",
]
