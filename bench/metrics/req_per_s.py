"""Requests answered correctly inside the window, per second of it."""


def read(run):
    return len(run.answered) / run.seconds
