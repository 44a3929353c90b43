"""Mean time from the decode program's last operation on the device to the
close of that iteration's ``drain`` span on the loop thread, over the traced
iterations: what the runtime and the D2H take to wake the loop once the device
is done. Nothing where the program keeps no spans or the two clocks cannot be
laid over each other (``harness/loop_spans.py lay_over``).

Reported only where the trace's own origin stood unmoved. Where the clocks
were laid together by moving or estimating the offset, the wake reads high by
the smallest lag between a ``dispatch`` span's opening and its program's start
(``loop_spans.traced``): the line then gives it as an upper bound beside the
sum no offset moves (wake + dispatch-to-start, over the iterations that
admitted no prompt), and nothing is reported."""

from harness import loop_spans


def read(ctx):
    t = loop_spans.traced(ctx)
    if not t or "wake_ms" not in t:
        return None
    line = (f"[bench] from the decode program's last operation to the close of its drain span: mean "
            f"{t['wake_ms']:.3f} ms, median {t['wake_median_ms']:.3f}, over {t['runs'] + 1} traced iterations; with "
            f"the time from the dispatch span's opening to the program's start {t['around_ms']:.3f} ms (no offset "
            f"between the clocks moves this sum); span clock: {t['how']}")
    if not t["exact"]:
        line += ("; drain_wake_ms.decode: not reported: the offset takes the smallest dispatch-to-start lag as "
                 "nothing, so the wake above is an upper bound")
    print(line, flush=True)
    return t["wake_ms"] if t["exact"] else None
