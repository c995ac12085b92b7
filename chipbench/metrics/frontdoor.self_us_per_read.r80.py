"""Host time per read in the front door's own code: the window's
``client.serve`` calls (the harness's profiler annotations around
``FrontDoor.serve``) less the ``engine.read_many`` span total, over the
reads in the window. It holds admission, queueing, group dispatch,
response assembly and, in a traced run, the front door's own spans."""

from harness import program_trace


def read(run):
    s = program_trace.front_door_s(run.trace, run.stages)
    return None if s is None or not run.reads else s * 1e6 / run.reads
