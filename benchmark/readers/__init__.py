"""Per-layer metric readers, one module each, named in the metric's file
``layer_metrics/<metric>.json``. ``read(r, args)`` takes the run's record
``r`` (window, reduced trace, set-up counters, device, configuration, peaks)
and the metric's own ``args``; it returns the number, or None where it finds
nothing to read, and the harness then leaves the metric out of the line."""
