"""One module per source kind. ``read(run, args)`` returns the value, or
``(value, samples)``, or None where there was nothing to read; the
harness then leaves the metric out of the line. A per-layer metric's
file (``benchmark/layer_metrics/<name>.json``) names its module and
gives the arguments."""
