"""One module per reader kind. A reader takes its parameters (the
``layer_metrics/<metric>.json`` file) and the run's context and returns the
metric's value, or None when what it reads is not there — the harness then
leaves the metric out of the line.

The context (``ctx``) is a dict:
  summary   stats.summarize() of the window before the slice   (driver side)
  spans     stats.span_stats() of the window before the slice  (driver side)
  samples   {name: [(t, value), ...]} per step   (driver side)
  counters  {"before": snapshot, "after": snapshot} of the program's registry
  trace     trace_reduce.reduce_trace() of the traced slice, or None
  slice     (t0, t1): the loop turns in which the profiler started and stopped,
            on the driver's clock, or None; the steps started in [t0, t1) are traced
  peaks     peaks of one chip, or None off the chip
  attrs     the model's config attributes
  chips     chips the model is sharded over
  kernels   {program: tpu_custom_call count} read from the executables
"""
