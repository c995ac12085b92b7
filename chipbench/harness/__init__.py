"""Chip benchmark harness for the HR-Cassandra store.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own (``configs/``, ``traffic/``,
``metrics/``) that :mod:`harness.spec` finds by the name
``BENCHMARK.json`` gives it; the modules here are the general parts:
the deployment builder, the traffic generator, the client loop, the
plain reference, the statistics and the reduction of a profiler trace.
"""
