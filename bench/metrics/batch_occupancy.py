"""Requests per dispatched batch over the window: the `ServerStats`
counters ``serving.completed`` / ``serving.batches``."""


def read(run):
    c = run.counters
    if not c or not c["batches"]:
        return None
    return c["completed"] / c["batches"]
