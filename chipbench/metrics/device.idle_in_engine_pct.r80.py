"""Share of the traced window in which the engine is reading and no
operation runs on the device, in %, averaged over the chips.

The idle time inside the window's ``client.serve`` calls, less the part
of those calls spent outside ``engine.read_many``: the front door
launches no device work of its own, so the device is idle there, and
the rest of the idle time inside the calls lies inside ``read_many``
(host planning, view folds, dispatch and host syncs)."""

from harness import program_trace


def read(run):
    t = run.trace
    front_door = program_trace.front_door_s(t, run.stages)
    if front_door is None or t.window_s <= 0:
        return None
    serve = program_trace.intervals(t.labels, program_trace.SERVE)
    return 100.0 * (program_trace.idle_in_s(t, serve) - front_door) / t.window_s
