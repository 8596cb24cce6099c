"""The benchmark's own code: everything a later PR may not change.

``run.py`` is the one command. Nothing in this package names a cell, a
configuration, a traffic mix or a metric: those are data files found by
the names in ``BENCHMARK.json`` (``manifest.py``). Nor does it name an
architecture: a configuration's file names the modules that hold its
plain reference (``benchmark/references``) and what its attention must
read and multiply (``benchmark/attention_costs``).
"""
