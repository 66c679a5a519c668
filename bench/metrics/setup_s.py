"""Seconds from process start to the first timed answer."""


def read(run):
    return run.spans.get("setup")
