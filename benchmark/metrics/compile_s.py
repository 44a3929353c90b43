"""Seconds of XLA backend compilation (a persistent-cache retrieval counts
as the short compile it is) from the harness's one ``CompileClock``, up to
the opening of the window."""


def read(ctx):
    return ctx["compile_s"]
