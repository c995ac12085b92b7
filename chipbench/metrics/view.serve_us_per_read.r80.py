"""Materialized-view serving per read: the ``view.serve`` span total
(window location, partial fold, boundary rescans with their host sync)
over the reads in the window."""


def read(run):
    return run.span_us_per_read("view.serve")
