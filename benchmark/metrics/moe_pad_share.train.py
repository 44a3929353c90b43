"""Rows of padding in the grouped expert products over the rows that are
real, over the window's steps: the recorder's rows carry the step's
``moe_pad_rows`` (rows between a group's end and its last tile's end, summed
over the routed layers) and ``moe_pairs_here`` (token-expert pairs computed
here). Nothing where the rows carry no such counter."""


def read(ctx):
    rows = ctx["recorder"].history["train"][ctx["first_step"]:ctx["last_step"] + 1]
    if not rows or any("moe_pad_rows" not in r or "moe_pairs_here" not in r for r in rows):
        return None
    real = sum(r["moe_pairs_here"] for r in rows)
    return 100.0 * sum(r["moe_pad_rows"] for r in rows) / real if real else None
