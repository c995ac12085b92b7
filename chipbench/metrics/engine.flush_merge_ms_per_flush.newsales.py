"""Host merge per replica flush: the ``engine.flush_merge`` span total
(``SortedTable.merge_run``: the sorted run merged into the replica's
host table, with the device append and view extension it does) over
its count in the window."""


def read(run):
    return run.span_ms_per_call("engine.flush_merge")
