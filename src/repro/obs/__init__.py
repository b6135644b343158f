"""repro.obs — mdmptrace: the observability subsystem (the SEVENTH
managed subsystem, cross-cutting the other six).

Four pieces, one loop:

* ``tracer``   — zero-dependency span tracer (bounded ring,
  thread-correct nesting, free when disabled, and on the profiler's
  clock while a ``jax.profiler`` session records);
* ``registry`` — ONE metrics registry (counters/histograms/EWMA/extrema)
  that serve/, checkpoint/ and the train loop build on;
* ``export``   — Chrome-trace-event/Perfetto JSON with per-mesh-axis
  comm tracks + DecisionRecord instants, and measured in-flight windows
  for mdmplint pass 4;
* ``calibrate``— the predicted-vs-measured ledger joining
  DecisionRecords to spans, plus the Recalibrator that triggers tuner
  re-resolution on sustained miscalibration.

Instrument -> cost-model -> decide -> **measure -> calibrate ->
re-resolve**: this package is the feedback edge the paper's managed
contract promises.
"""

from repro.obs.calibrate import (CalibrationLedger, CalibrationSample,
                                 Recalibrator, chosen_predicted_s,
                                 cover_with)
from repro.obs.export import (load_trace, measured_windows,
                              to_chrome_trace, trace_tracks,
                              write_chrome_trace)
from repro.obs.registry import (Counter, Ewma, Extremum, Histogram,
                                MetricsRegistry)
from repro.obs.tracer import (NULL, NullTracer, Span, Tracer,
                              dispatch_span, get_tracer, install_tracer,
                              use_tracer)

__all__ = [
    "CalibrationLedger", "CalibrationSample", "Recalibrator",
    "chosen_predicted_s", "cover_with",
    "load_trace", "measured_windows", "to_chrome_trace", "trace_tracks",
    "write_chrome_trace",
    "Counter", "Ewma", "Extremum", "Histogram", "MetricsRegistry",
    "NULL", "NullTracer", "Span", "Tracer", "dispatch_span",
    "get_tracer", "install_tracer", "use_tracer",
]
