"""Cuts a recorded ``*.xplane.pb`` to a few whole steps of one chip and writes
it as an XSpace text proto small enough to commit:

    python3 benchmark/fixtures/cut_trace.py <trace.xplane.pb> <out.xspace.txt> [steps]

Only the device plane's ``XLA Modules`` and ``XLA Ops`` lines are kept, op
names cut to 160 characters, times rebased to the first kept step.
"""

import sys
from collections import defaultdict


def main(src, dst, steps=3):
    from jax.profiler import ProfileData

    plane = next(p for p in ProfileData.from_file(src).planes if p.name == "/device:TPU:0")
    lines = {ln.name: [(e.name, e.start_ns, e.duration_ns) for e in ln.events] for ln in plane.lines}
    total = defaultdict(float)
    for n, _, d in lines["XLA Modules"]:
        total[n] += d
    step = max(total, key=total.get)
    starts = sorted(s for n, s, _ in lines["XLA Modules"] if n == step)
    k = max(0, (len(starts) - steps - 1) // 2)
    lo, hi = starts[k], starts[k + steps]
    ids, out = {}, [f'planes {{\n  id: 1 name: "/device:TPU:0"']
    for i, name in enumerate(("XLA Modules", "XLA Ops"), 1):
        out.append(f'  lines {{ id: {i} name: "{name}" timestamp_ns: 0')
        for n, s, d in lines[name]:
            # whole events only, and the run of the step program that closes the window
            if s >= lo and (s + d <= hi or (name == "XLA Modules" and s == hi)):
                mid = ids.setdefault(n[:160], len(ids) + 1)
                out.append(f"    events {{ metadata_id: {mid} offset_ps: {round((s - lo) * 1000)} "
                           f"duration_ps: {round(d * 1000)} }}")
        out.append("  }")
    for n, mid in ids.items():
        name = n.replace("\\", "\\\\").replace('"', '\\"')
        out.append(f'  event_metadata {{ key: {mid} value {{ id: {mid} name: "{name}" }} }}')
    out.append("}")
    with open(dst, "w") as f:
        f.write("\n".join(out) + "\n")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *(int(a) for a in sys.argv[3:4]))
