"""Span of the warm-up answer: a compile, or a compile-cache load, plus
one answer of the cell's shapes."""


def read(run):
    return run.spans.get("warmup")
