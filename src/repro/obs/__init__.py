"""repro.obs — two-plane observability for the simulation pipeline.

**Host plane** (:mod:`repro.obs.spans` + :mod:`repro.obs.export`): a
process-wide span tracer instrumenting `union.run` end-to-end — planner
lowering, engine-cache gets, cold/warm engine execution, windowed
scheduler loops — exported as Chrome trace-event JSON (Perfetto) or a
structured JSONL run log, plus the leveled run logger ``log`` that
replaces stray prints across the CLI/scheduler/launch layers.

**Sim plane** (:mod:`repro.obs.probes` + :mod:`repro.obs.hist` +
:mod:`repro.obs.timeline`): fixed-size ring buffers inside ``SimState``
sampling per-level link utilization, per-app in-flight latency, pool
occupancy, and queue depth every K live ticks; full-fidelity
per-(app, link-level) latency histograms with exact streaming moments;
and sim-time job lifecycle timelines recorded by the scheduler loop
(arrival → queue → backfill → run → drain) exported as a second Chrome
trace over *virtual* time. All compiled/recorded only when requested
(:class:`ProbeConfig` / :class:`HistConfig` select separate engine-cache
entries), so the plain engine stays bit-identical to its goldens.

**Process plane** (:mod:`repro.obs.metrics`): a process-wide metrics
registry (counters / gauges / histograms) with OpenMetrics text export —
the scrape surface for long campaigns and a future persistent server.

See ``docs/obs.md`` for the span taxonomy and buffer/accumulator layouts.
"""
from repro.obs.spans import (  # noqa: F401
    Tracer, get_tracer, enable, disable, tracing,
    span, counter, summarize, note_program, device_phases,
)
from repro.obs.export import (  # noqa: F401
    log, get_logger, set_verbosity, log_to_jsonl,
    chrome_events, write_chrome_trace, write_jsonl,
)
from repro.obs.probes import (  # noqa: F401
    ProbeConfig, ProbeState, init_probes, sample_probes,
    ring_order, probe_timelines,
)
from repro.obs.hist import (  # noqa: F401
    HistConfig, HistState, bucket_of, init_hist, update_hist, merge_hist,
    hist_summary,
)
from repro.obs.timeline import (  # noqa: F401
    TimelineRecorder, sim_chrome_trace, write_sim_trace,
)
from repro.obs.metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, Progress,
    get_registry, write_openmetrics,
)

__all__ = [
    "Tracer", "get_tracer", "enable", "disable", "tracing",
    "span", "counter", "summarize", "note_program", "device_phases",
    "log", "get_logger", "set_verbosity", "log_to_jsonl",
    "chrome_events", "write_chrome_trace", "write_jsonl",
    "ProbeConfig", "ProbeState", "init_probes", "sample_probes",
    "ring_order", "probe_timelines",
    "HistConfig", "HistState", "bucket_of", "init_hist", "update_hist",
    "merge_hist", "hist_summary",
    "TimelineRecorder", "sim_chrome_trace", "write_sim_trace",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Progress",
    "get_registry", "write_openmetrics",
]
