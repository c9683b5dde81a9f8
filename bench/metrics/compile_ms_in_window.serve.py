"""Milliseconds the executor spent realizing programs inside the
window (``compile_ms_total`` delta): 0 when set-up warmed every shape."""


def read(ctx):
    return ctx["executor"]["compile_ms_total"]
