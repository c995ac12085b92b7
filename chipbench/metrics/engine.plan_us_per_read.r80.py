"""Host planning per read: the ``engine.plan`` span total (cost model
and replica pick inside ``read_many``) over the reads in the window."""


def read(run):
    return run.span_us_per_read("engine.plan")
