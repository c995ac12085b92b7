"""Mean reads per ``read_many`` call: the front door's ``submitted``
counter over its ``batches`` counter in the window."""


def read(run):
    batches = run.counters.get("batches", 0)
    return run.counters["submitted"] / batches if batches else None
