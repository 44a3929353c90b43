"""Mean time a step waited on the loader's queue: the recorder's ``wait``
brackets (host clock around the blocking fetch) over the window's steps."""


def read(ctx):
    waits = ctx["recorder"].timings.get("wait", [])[ctx["first_step"]:ctx["last_step"] + 1]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
