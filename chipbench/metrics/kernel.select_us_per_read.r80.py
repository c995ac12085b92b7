"""Select compaction per read: the ``kernel.select_compact`` span total
(launch plus the host sync that fetches the row indices) over the reads
in the window."""


def read(run):
    return run.span_us_per_read("kernel.select_compact")
