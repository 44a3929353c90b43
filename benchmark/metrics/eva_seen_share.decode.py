"""The rows an EVA decode query reads over its context: over the traced
iterations, the program's own counts (``eva_window_positions``: its window
block's exact keys, itself included, and ``eva_compressed_rows``: one row a
chunk of every earlier block, summed over the running slots an iteration in
the serving loop's span store; the gauges ``tmpi_decode_eva_*`` are their
means) over the probe's sum of the running sequences' contexts. About 10 %
at 20-26k positions: what the compressed rows save against full attention,
which reads 100 %. Moves ``decode_tokens_per_s``. Nothing (never 0) where the
program keeps no such counts."""

from harness import manifest

_rows = manifest.load_module("metrics", "eva_decode_roofline.decode")  # the traced rows and the program's counts


def read(ctx):
    if not ctx["trace"] or ctx["traced_first"] is None:
        return None
    rows = _rows.traced_rows(ctx)
    got = _rows.counts(ctx, rows) if rows else None
    context = sum(r[7] for r in rows)
    if got is None or not context:
        return None
    return 100.0 * sum(sum(v) for v in got.values()) / context
