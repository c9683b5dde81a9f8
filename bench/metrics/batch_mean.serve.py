"""Queries per coalesced read dispatch of the serve scheduler
(``serve/scheduler.py``): ``reads`` / ``read_batches`` over the window."""


def read(ctx):
    s = ctx["scheduler"]
    return s["reads"] / s["read_batches"] if s["read_batches"] else None
