"""Flight recorder for the batched engine: telemetry, profiling, export.

The paper's thesis is that ZNS zone management imposes *hidden* costs --
DLWA, wear and interference the host cannot see until tail latency blows
up.  End-of-run scalars (``ZoneEngine.metrics``,
``runner.config_report``) reproduce the paper's aggregates but hide the
*temporal* structure: a fleet run that writes superfluously in one
occupancy band looks identical to a healthy one.  This package makes
the hidden costs visible without giving up the one-dispatch execution
model:

* :mod:`repro.obs.recorder` -- an opt-in pure-JAX telemetry accumulator
  carried through the ``run_program(s)`` scan (``ObsConfig``):
  per-op host/superfluous pages, wear, occupancy and legality binned
  into fixed-size time-bucketed histograms per lane, plus host-side
  decoding into per-tenant / per-zone / per-device timeline dicts
  (plain lists, no pandas);
* :mod:`repro.obs.profile`  -- dispatch-level profiling: wall time
  split into trace/lower/compile vs execute via the ``jax.monitoring``
  compile events, a recompile counter over the jit caches (keyed on
  abstract input signatures), and per-section counters.  Library code
  marks its host work with ``span(name)``: a section of the profiler
  it is given, else of the *current* one (the profiler whose section
  is open), else nothing.  A span whose name is already open on that
  profiler is not entered again, so rollups that call each other are
  counted once.  The fleet runner, the evaluator and the replay take a
  ``profiler=``; a check or rollup called inside any section finds the
  current one.  ``count(name, n)`` adds to a program counter of the
  same profiler (``Profiler.counters``), which ``emit_fleet_obs``
  writes into its metrics registry;
* :mod:`repro.obs.export`   -- Chrome/Perfetto ``trace_event`` JSON
  export (tenants -> tracks, ops -> duration events on the
  ``timing.simulate_fleet_ops`` clock) plus a counters/gauges metrics
  registry sidecar, schema-validated against
  ``docs/schema/perfetto_trace.schema.json``.

Entry points: ``benchmarks/fleet_search.py --obs`` (emit trace +
telemetry for a search run), ``tools/obs_report.py`` (render the
telemetry as a markdown report), and ``bench/run.py --trace 1`` (the
chip benchmark's per-layer metrics, read from the program spans).  The
recorder is effect-free on device results: telemetry-on and
telemetry-off runs produce bit-identical ``DeviceState`` /
``OpTrace`` (property-tested in ``tests/test_obs.py``).
"""

from repro.obs.export import (MetricsRegistry, emit_fleet_obs,
                              fleet_trace_events, load_trace_schema,
                              validate_trace, write_trace)
from repro.obs.profile import (COMPILE_LOG, CompileLog, Profiler,
                               RecompileCounter, count, jit_cache_size,
                               span)
from repro.obs.recorder import (ObsConfig, TelemetryState,
                                device_rollup, fleet_timelines,
                                lane_timeline, telemetry_init,
                                telemetry_update, tenant_timelines,
                                zone_timelines)

__all__ = [
    "ObsConfig", "TelemetryState", "telemetry_init", "telemetry_update",
    "lane_timeline", "fleet_timelines", "tenant_timelines",
    "zone_timelines", "device_rollup",
    "COMPILE_LOG", "CompileLog", "Profiler", "RecompileCounter",
    "count", "jit_cache_size", "span",
    "MetricsRegistry", "fleet_trace_events", "write_trace",
    "validate_trace", "load_trace_schema", "emit_fleet_obs",
]
