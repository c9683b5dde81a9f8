"""Blocking host readbacks per compiled-program launch of the executor
(``core/executor.py``): (``host_syncs`` + ``probe_syncs``) /
``dispatches`` over the window."""


def read(ctx):
    e = ctx["executor"]
    if not e["dispatches"]:
        return None
    return (e["host_syncs"] + e["probe_syncs"]) / e["dispatches"]
