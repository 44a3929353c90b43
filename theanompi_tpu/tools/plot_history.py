"""Plot Recorder histories — the reference's repo-root plot helper,
TPU-native edition.

Reference: the ``show.py``-style script next to ``lib/recorder.py``
(SURVEY.md §1 L8 / §5.1): it loaded the recorder's pickled history and
plotted cost/error curves for one or more runs. Same contract here,
over the Recorder's JSONL stream (``<save_dir>/<run>.jsonl``,
`utils/recorder.py`): train loss + LR per step, val loss/error per
epoch, and images/sec — for any number of runs on shared axes, so
sync-rule comparisons (the reference's main use: BSP vs EASGD curves)
are one command:

    python -m theanompi_tpu.tools.plot_history experiments/results/bsp \\
        experiments/results/easgd -o rules.png

Accepts run directories (every ``*.jsonl`` inside) or ``.jsonl`` files.
Headless-safe (Agg backend); ``--show`` opens a window where a display
exists.

When a run was recorded with ``--obs-dir`` pointing INSIDE its save
dir (an ``obs/`` directory next to the run JSONL), a third panel row
appears: achieved interconnect GB/s per step (obs/metrics.jsonl
snapshots) and per-kind span time fractions (the ``span_summary`` line
of obs/spans_rank*.jsonl). Runs recorded with ``--numerics-freq`` add
a FOURTH row from ``obs/numerics_rank0.jsonl``: grad/update norms
(left, log scale) and the per-rule divergence gauge (right), with
detected anomaly steps marked as vertical lines on both. Runs whose
engine declared a cost model add an ATTRIBUTION row from the
``kind=profile`` records (obs/attribution.py): stacked step-time
fractions (compute/comm/host/residual — where the step goes) on the
left, the MFU trend (spec MFU, or the calibrated stand-in dashed) on
the right. Runs watched by a record-writing FleetTailer (obs/fleet.py:
the chief exporter) add a FLEET row from ``obs/fleet.jsonl``: the
per-rank step-time spread band (min..max over ranks, median line) with
red vlines where the persistent-straggler detector fired (left), and
the frozen/silent-rank count (right) — append-mode rerun safe like the
comm panel. Runs whose drift watchdog wrote ``kind=drift`` records
(obs/drift.py) add a DRIFT row: the EWMA relative error per truth
source (cost/traffic/memory, log scale) with red vlines where the
watchdog breached tolerance (left) and the cumulative breach count
(right) — append-mode rerun safe like every other obs panel. Runs
without obs/numerics/profile/fleet/drift data plot exactly as before —
extra rows only render when at least one run has them.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys


def load_jsonl(path: str) -> dict:
    """Split one Recorder JSONL into train/val series."""
    train: dict[str, list] = {"step": [], "loss": [], "error": [],
                              "lr": [], "images_per_sec": [],
                              "ips_step": []}
    val: dict[str, list] = {"epoch": [], "loss": [], "error": []}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            row = json.loads(line)
            if row.get("kind") == "train":
                for k in train:
                    if k in row:
                        train[k].append(row[k])
                # throughput can be SPARSE (a row carries it only when
                # its step reported images): pair it with its own step
                # axis, never the full step list
                if "images_per_sec" in row and "step" in row:
                    train["ips_step"].append(row["step"])
            elif row.get("kind") == "val":
                for k in val:
                    if k in row:
                        val[k].append(row[k])
    return {"train": train, "val": val}


def load_obs(jsonl_path: str) -> dict:
    """Obs-subsystem series for the run at ``jsonl_path``: looks for an
    ``obs/`` directory next to the run JSONL (the ``--obs-dir`` inside
    the save dir convention). Returns ``{"comm_step": [...],
    "comm_gbps": [...], "fractions": {kind: frac}}`` — empty lists/dict
    when the run has no (or unreadable) obs data, so callers degrade
    gracefully."""
    out: dict = {"comm_step": [], "comm_gbps": [], "comm_gbps_raw": [],
                 # per-link-class series (multislice runs): the ICI and
                 # DCN shares of the achieved rate, paired with
                 # comm_step like the raw series (None when absent)
                 "comm_gbps_ici": [], "comm_gbps_dcn": [],
                 "codec": None, "fractions": {},
                 # step-time attribution (kind=profile records,
                 # obs/attribution.py): stacked fractions + MFU trend
                 "prof_step": [], "prof_fracs": [], "prof_mfu": [],
                 "prof_mfu_calibrated": [],
                 # model-drift watchdog (kind=drift records,
                 # obs/drift.py): EWMA relative error per truth source,
                 # None-paired with drift_step like the comm series
                 "drift_step": [], "drift_cost": [], "drift_traffic": [],
                 "drift_memory": [], "drift_breach_steps": []}
    obs_dir = os.path.join(os.path.dirname(os.path.abspath(jsonl_path)), "obs")
    metrics = os.path.join(obs_dir, "metrics.jsonl")
    if os.path.exists(metrics):
        try:
            with open(metrics) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    row = json.loads(line)
                    if row.get("kind") == "comm":
                        # the run's wire declaration (last wins, like
                        # the span summary): names the codec for the
                        # legend of the raw-vs-effective pair
                        out["codec"] = row.get("codec")
                        continue
                    if row.get("kind") == "profile" and "step" in row:
                        if out["prof_step"] and (
                            row["step"] < out["prof_step"][-1]
                        ):
                            # append-mode rerun: newest run's series
                            # wins (mirrors the comm-series rule)
                            for k in ("prof_step", "prof_fracs",
                                      "prof_mfu", "prof_mfu_calibrated"):
                                out[k] = []
                        if out["prof_step"] and (
                            row["step"] == out["prof_step"][-1]
                        ):
                            out["prof_step"].pop()
                            out["prof_fracs"].pop()
                            out["prof_mfu"].pop()
                            out["prof_mfu_calibrated"].pop()
                        out["prof_step"].append(row["step"])
                        out["prof_fracs"].append(row.get("fractions", {}))
                        out["prof_mfu"].append(row.get("mfu"))
                        out["prof_mfu_calibrated"].append(
                            row.get("mfu_calibrated")
                        )
                        continue
                    if row.get("kind") == "drift" and "step" in row:
                        if out["drift_step"] and (
                            row["step"] < out["drift_step"][-1]
                        ):
                            # append-mode rerun: newest run's series
                            # wins (mirrors the comm-series rule)
                            for k in ("drift_step", "drift_cost",
                                      "drift_traffic", "drift_memory",
                                      "drift_breach_steps"):
                                out[k] = []
                        if out["drift_step"] and (
                            row["step"] == out["drift_step"][-1]
                        ):
                            # change-gated re-emit at an unchanged step
                            # (EWMA moved between drains): newest wins
                            for k in ("drift_step", "drift_cost",
                                      "drift_traffic", "drift_memory"):
                                out[k].pop()
                        out["drift_step"].append(row["step"])
                        out["drift_cost"].append(row.get("model_err_cost"))
                        out["drift_traffic"].append(
                            row.get("model_err_traffic"))
                        out["drift_memory"].append(
                            row.get("model_err_memory"))
                        if row.get("breached"):
                            out["drift_breach_steps"].append(row["step"])
                        continue
                    if row.get("kind") != "metrics" or "step" not in row:
                        continue
                    gbps = row.get("metrics", {}).get("tmpi_comm_gbps")
                    raw = row.get("metrics", {}).get("tmpi_comm_gbps_raw")
                    ici = row.get("metrics", {}).get("tmpi_comm_ici_gbps")
                    dcn = row.get("metrics", {}).get("tmpi_comm_dcn_gbps")
                    if gbps is not None:
                        if out["comm_step"] and row["step"] < out["comm_step"][-1]:
                            # append-mode rerun into the same obs dir:
                            # the step counter restarted — keep only the
                            # newest run's series (mirrors the
                            # last-summary-wins rule below)
                            out["comm_step"], out["comm_gbps"] = [], []
                            out["comm_gbps_raw"] = []
                            out["comm_gbps_ici"] = []
                            out["comm_gbps_dcn"] = []
                        if out["comm_step"] and row["step"] == out["comm_step"][-1]:
                            # epoch-end snapshot repeats the step of the
                            # last per-step snapshot: newest value wins
                            out["comm_gbps"][-1] = gbps
                            out["comm_gbps_raw"][-1] = raw
                            out["comm_gbps_ici"][-1] = ici
                            out["comm_gbps_dcn"][-1] = dcn
                        else:
                            out["comm_step"].append(row["step"])
                            out["comm_gbps"].append(gbps)
                            # paired with comm_step even when absent
                            # (codec-off runs): None rows drop at plot
                            out["comm_gbps_raw"].append(raw)
                            out["comm_gbps_ici"].append(ici)
                            out["comm_gbps_dcn"].append(dcn)
        except (OSError, ValueError):
            pass  # partial/corrupt telemetry: plot what parses
    # rank 0's trace is the driver view; one bar set per run
    span_files = sorted(glob.glob(os.path.join(obs_dir, "spans_rank*.jsonl")))
    if span_files:
        try:
            with open(span_files[0]) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    row = json.loads(line)
                    if row.get("kind") == "span_summary":
                        # last summary wins (append-mode reruns into the
                        # same dir stack summaries; newest describes the
                        # most recent run)
                        out["fractions"] = row.get("fractions", {})
        except (OSError, ValueError):
            pass
    # numerics flight-recorder telemetry (obs/numerics.py): sentinel
    # rows -> norm/divergence curves, anomaly records -> step markers
    out.update({"nm_step": [], "grad_norm": [], "update_norm": [],
                "div_step": [], "divergence": [], "anomaly_steps": []})
    numerics = os.path.join(obs_dir, "numerics_rank0.jsonl")
    if os.path.exists(numerics):
        try:
            with open(numerics) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    row = json.loads(line)
                    if row.get("kind") == "numerics":
                        m = row.get("metrics", {})
                        if "nm_grad_norm" in m:
                            out["nm_step"].append(row["step"])
                            out["grad_norm"].append(m["nm_grad_norm"])
                            out["update_norm"].append(
                                m.get("nm_update_norm", float("nan"))
                            )
                        if "nm_divergence" in m:
                            out["div_step"].append(row["step"])
                            out["divergence"].append(m["nm_divergence"])
                    elif row.get("kind") == "anomaly":
                        out["anomaly_steps"].append(row["step"])
        except (OSError, ValueError):
            pass  # partial/corrupt telemetry: plot what parses
    # fleet telemetry (obs/fleet.py kind=fleet records): per-rank
    # step-time spread band (min/median/max over ranks) + the steps
    # where the persistent-straggler detector fired
    out.update({"fleet_step": [], "fleet_min": [], "fleet_p50": [],
                "fleet_max": [], "fleet_frozen": [],
                "straggler_steps": []})
    fleet = os.path.join(obs_dir, "fleet.jsonl")
    if os.path.exists(fleet):
        try:
            with open(fleet) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    row = json.loads(line)
                    if row.get("kind") != "fleet" or "step" not in row:
                        continue
                    if out["fleet_step"] and (
                        row["step"] < out["fleet_step"][-1]
                    ):
                        # append-mode rerun into the same obs dir: the
                        # step counter restarted — newest run's series
                        # wins (mirrors the comm-series rule)
                        for k in ("fleet_step", "fleet_min", "fleet_p50",
                                  "fleet_max", "fleet_frozen",
                                  "straggler_steps"):
                            out[k] = []
                    if out["fleet_step"] and (
                        row["step"] == out["fleet_step"][-1]
                    ):
                        # flag-change record at an unchanged step:
                        # newest values win
                        for k in ("fleet_step", "fleet_min", "fleet_p50",
                                  "fleet_max", "fleet_frozen"):
                            out[k].pop()
                    out["fleet_step"].append(row["step"])
                    out["fleet_min"].append(
                        row.get("step_seconds_min", 0.0))
                    out["fleet_p50"].append(
                        row.get("step_seconds_p50", 0.0))
                    out["fleet_max"].append(
                        row.get("step_seconds_max", 0.0))
                    out["fleet_frozen"].append(
                        len([r for r in (row.get("frozen") or "").split(",")
                             if r]))
                    if row.get("straggler_count", 0) or row.get("stragglers"):
                        out["straggler_steps"].append(row["step"])
        except (OSError, ValueError):
            pass  # partial/corrupt telemetry: plot what parses
    return out


def discover(paths: list[str]) -> dict[str, str]:
    """``{label: jsonl_path}`` from a mix of dirs and files. Labels that
    collide (two ``run.jsonl`` inputs, or identically-named dirs) are
    ALL relabeled with the shortest path suffix that tells them apart —
    every requested run appears, unambiguously."""
    pairs: list[tuple[str, str]] = []
    for p in paths:
        if os.path.isdir(p):
            found = sorted(glob.glob(os.path.join(p, "*.jsonl")))
            if not found:
                raise FileNotFoundError(f"no *.jsonl under {p!r}")
            for f in found:
                label = os.path.basename(os.path.dirname(f)) or \
                    os.path.splitext(os.path.basename(f))[0]
                if len(found) > 1:
                    label = os.path.splitext(os.path.basename(f))[0]
                pairs.append((label, f))
        else:
            pairs.append((os.path.splitext(os.path.basename(p))[0], p))

    def suffix(f: str, k: int) -> str:
        parts = os.path.normpath(os.path.abspath(f)).split(os.sep)
        return "/".join(parts[-k:])

    from collections import Counter

    # identity is the resolved path: the same file listed under two
    # spellings ('expA/x.jsonl' and './expA/x.jsonl') is ONE run, and
    # only genuinely different files count as peers to disambiguate
    seen: set = set()
    uniq = []
    for lbl, f in pairs:
        key = os.path.abspath(f)
        if key not in seen:
            seen.add(key)
            uniq.append((lbl, f))
    counts = Counter(lbl for lbl, _ in uniq)
    runs: dict[str, str] = {}
    for lbl, f in uniq:
        if counts[lbl] > 1:
            peers = [
                g for l2, g in uniq
                if l2 == lbl and os.path.abspath(g) != os.path.abspath(f)
            ]
            k = 2
            while any(suffix(g, k) == suffix(f, k) for g in peers):
                k += 1
            lbl = suffix(f, k)
        if lbl in runs:  # safety net: never drop a requested run
            base, i = lbl, 2
            while lbl in runs:
                lbl, i = f"{base}#{i}", i + 1
        runs[lbl] = f
    return runs


def plot(runs: dict[str, str], out: str, show: bool = False,
         smooth: int = 1) -> str:
    import matplotlib

    if not show:
        matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    def smoothed(xs, ys, k):
        if k <= 1 or len(ys) < k:
            return xs, ys
        acc, out_x, out_y = 0.0, [], []
        for i, y in enumerate(ys):
            acc += y
            if i >= k:
                acc -= ys[i - k]
            if i >= k - 1:
                out_x.append(xs[i])
                out_y.append(acc / k)
        return out_x, out_y

    obs = {label: load_obs(path) for label, path in runs.items()}
    has_obs = any(
        o["comm_gbps"] or o["fractions"] for o in obs.values()
    )
    has_nm = any(
        o["nm_step"] or o["div_step"] or o["anomaly_steps"]
        for o in obs.values()
    )
    has_prof = any(o["prof_step"] for o in obs.values())
    has_fleet = any(o["fleet_step"] for o in obs.values())
    has_drift = any(o["drift_step"] for o in obs.values())
    n_rows = (2 + int(has_obs) + int(has_nm) + int(has_prof)
              + int(has_fleet) + int(has_drift))
    fig, axes = plt.subplots(n_rows, 2, figsize=(11, 3.5 * n_rows))
    (ax_loss, ax_val), (ax_ips, ax_lr) = axes[0], axes[1]
    ax_comm = ax_frac = ax_nm = ax_div = ax_attr = ax_mfu = None
    ax_fleet = ax_frozen = ax_drift = ax_breach = None
    row = 2
    if has_obs:
        ax_comm, ax_frac = axes[row]
        row += 1
    if has_nm:
        ax_nm, ax_div = axes[row]
        row += 1
    if has_prof:
        ax_attr, ax_mfu = axes[row]
        row += 1
    if has_fleet:
        ax_fleet, ax_frozen = axes[row]
        row += 1
    if has_drift:
        ax_drift, ax_breach = axes[row]
    frac_kinds: list[str] = []
    for o in obs.values():
        frac_kinds += [k for k in o["fractions"] if k not in frac_kinds]
    for run_i, (label, path) in enumerate(runs.items()):
        h = load_jsonl(path)
        t, v = h["train"], h["val"]
        o = obs[label]
        if ax_comm is not None and o["comm_gbps"]:
            eff_label = (
                f"{label} ({o['codec']} wire)"
                if o.get("codec") and o["codec"] != "none" else label
            )
            line, = ax_comm.plot(
                *smoothed(o["comm_step"], o["comm_gbps"], smooth),
                label=eff_label,
            )
            raw_pairs = [
                (s, v) for s, v in zip(o["comm_step"], o["comm_gbps_raw"])
                if v is not None
            ]
            if raw_pairs:
                # effective vs raw: the vertical gap IS the codec win —
                # dashed raw in the same color so runs stay grouped
                rs, rv = zip(*raw_pairs)
                ax_comm.plot(*smoothed(list(rs), list(rv), smooth),
                             linestyle="--", color=line.get_color(),
                             alpha=0.6, label=f"{label} raw fp32")
            # per-link-class split (multislice runs): ICI dotted, DCN
            # dash-dot in the run's color — the DCN series is the one
            # a wire codec visibly pulls down on the hierarchical rule
            for key, style, cls in (("comm_gbps_ici", ":", "ici"),
                                    ("comm_gbps_dcn", "-.", "dcn")):
                pairs = [(s, v) for s, v in zip(o["comm_step"], o[key])
                         if v is not None]
                if pairs:
                    ls, lv = zip(*pairs)
                    ax_comm.plot(*smoothed(list(ls), list(lv), smooth),
                                 linestyle=style, color=line.get_color(),
                                 alpha=0.8, label=f"{label} {cls}")
        if ax_frac is not None and o["fractions"]:
            # grouped bars: one cluster per span kind, one bar per run
            width = 0.8 / max(1, len(runs))
            xs = [frac_kinds.index(k) + run_i * width
                  for k in o["fractions"]]
            ax_frac.bar(xs, list(o["fractions"].values()), width=width,
                        label=label)
        if ax_nm is not None and o["nm_step"]:
            ax_nm.plot(*smoothed(o["nm_step"], o["grad_norm"], smooth),
                       label=f"{label} grad")
            ax_nm.plot(*smoothed(o["nm_step"], o["update_norm"], smooth),
                       label=f"{label} update", linestyle="--")
        if ax_div is not None and o["div_step"]:
            ax_div.plot(*smoothed(o["div_step"], o["divergence"], smooth),
                        label=label)
        if ax_attr is not None and o["prof_step"]:
            # stacked step-time fractions (kind=profile records): the
            # stack IS the step — where each step's wall went; residual
            # clamps at 0 for display (a negative residual means the
            # models over-explain, already flagged in the record)
            kinds = ("compute", "comm", "host", "residual")
            series = [
                [max(0.0, f.get(k, 0.0)) for f in o["prof_fracs"]]
                for k in kinds
            ]
            ax_attr.stackplot(
                o["prof_step"], series, alpha=0.7,
                labels=[f"{label} {k}" for k in kinds]
                if len(runs) > 1 else list(kinds),
            )
        if ax_mfu is not None and o["prof_step"]:
            spec = [(s, v) for s, v in zip(o["prof_step"], o["prof_mfu"])
                    if v is not None]
            cal = [(s, v) for s, v in
                   zip(o["prof_step"], o["prof_mfu_calibrated"])
                   if v is not None]
            if spec:
                ax_mfu.plot(*zip(*spec), label=f"{label} mfu")
            if cal:
                # the calibrated stand-in (no spec peak): dashed so it
                # cannot be misread as a real utilization number
                ax_mfu.plot(*zip(*cal), linestyle="--",
                            label=f"{label} mfu (calibrated)")
        if ax_fleet is not None and o["fleet_step"]:
            # spread band: min..max step time over ranks, median on top —
            # a widening band IS the straggler story at a glance
            ax_fleet.fill_between(o["fleet_step"], o["fleet_min"],
                                  o["fleet_max"], alpha=0.25,
                                  label=f"{label} min..max")
            ax_fleet.plot(o["fleet_step"], o["fleet_p50"],
                          label=f"{label} median")
            for j, s in enumerate(sorted(set(o["straggler_steps"]))):
                ax_fleet.axvline(
                    s, color="red", alpha=0.5, linestyle="-",
                    label=f"{label} straggler" if j == 0 else None)
        if ax_frozen is not None and o["fleet_step"]:
            ax_frozen.step(o["fleet_step"], o["fleet_frozen"],
                           where="post", label=f"{label} frozen ranks")
        if ax_drift is not None and o["drift_step"]:
            # one curve per truth source; zeros (a momentarily perfect
            # model) drop rather than fight the log axis
            for key, name, style in (("drift_cost", "cost", "-"),
                                     ("drift_traffic", "traffic", "--"),
                                     ("drift_memory", "memory", ":")):
                pairs = [(s, v) for s, v in zip(o["drift_step"], o[key])
                         if v is not None and v > 0]
                if pairs:
                    ax_drift.plot(*zip(*pairs), linestyle=style,
                                  label=f"{label} {name}")
            for j, s in enumerate(sorted(set(o["drift_breach_steps"]))):
                ax_drift.axvline(
                    s, color="red", alpha=0.5,
                    label=f"{label} breach" if j == 0 else None)
        if ax_breach is not None and o["drift_step"]:
            bset = set(o["drift_breach_steps"])
            cum, n = [], 0
            for s in o["drift_step"]:
                n += int(s in bset)
                cum.append(n)
            ax_breach.step(o["drift_step"], cum, where="post",
                           label=f"{label} breaches")
        if o["anomaly_steps"]:
            # anomaly markers on both numerics panels: first marker per
            # run carries the legend entry, the rest stay unlabeled
            for ax in (ax_nm, ax_div):
                if ax is None:
                    continue
                for j, s in enumerate(sorted(set(o["anomaly_steps"]))):
                    ax.axvline(s, color="red", alpha=0.4, linestyle=":",
                               label=f"{label} anomaly" if j == 0 else None)
        if t["step"] and t["loss"]:
            ax_loss.plot(*smoothed(t["step"], t["loss"], smooth), label=label)
        if v["epoch"]:
            # presence, not truthiness: an all-zero error series (a run
            # that reached 0% val error) is still the error curve
            key = "error" if len(v["error"]) == len(v["epoch"]) else "loss"
            ax_val.plot(v["epoch"], v[key], marker="o", label=f"{label} ({key})")
        if t["ips_step"] and t["images_per_sec"]:
            ax_ips.plot(*smoothed(t["ips_step"], t["images_per_sec"], smooth),
                        label=label)
        if t["step"] and t["lr"]:
            ax_lr.plot(t["step"][: len(t["lr"])], t["lr"], label=label)
    ax_loss.set(title="train loss", xlabel="step")
    ax_val.set(title="validation", xlabel="epoch")
    ax_ips.set(title="throughput (images/sec)", xlabel="step")
    ax_lr.set(title="learning rate", xlabel="step")
    all_axes = [ax_loss, ax_val, ax_ips, ax_lr]
    if ax_comm is not None:
        ax_comm.set(title="interconnect GB/s (effective solid, raw fp32 "
                          "dashed — gap = codec win; ici dotted / dcn "
                          "dash-dot on multislice runs)",
                    xlabel="step")
        ax_frac.set(title="span time fractions (of run wall clock)")
        if frac_kinds:
            ax_frac.set_xticks(range(len(frac_kinds)))
            ax_frac.set_xticklabels(frac_kinds, rotation=30, ha="right",
                                    fontsize=8)
        all_axes += [ax_comm, ax_frac]
    if ax_nm is not None:
        ax_nm.set(title="grad/update norm (numerics sentinels)",
                  xlabel="step")
        if ax_nm.lines:
            ax_nm.set_yscale("log")  # norms span orders of magnitude
        ax_div.set(title="divergence gauge (anomaly steps dotted red)",
                   xlabel="step")
        all_axes += [ax_nm, ax_div]
    if ax_attr is not None:
        ax_attr.set(title="step-time attribution "
                          "(compute/comm/host/residual fractions)",
                    xlabel="step", ylim=(0, 1.05))
        ax_mfu.set(title="MFU trend (dashed = calibrated peak)",
                   xlabel="step")
        all_axes += [ax_attr, ax_mfu]
    if ax_fleet is not None:
        ax_fleet.set(title="fleet step-time spread (band min..max over "
                           "ranks; red = persistent straggler)",
                     xlabel="step")
        ax_frozen.set(title="frozen (silent) ranks", xlabel="step")
        all_axes += [ax_fleet, ax_frozen]
    if ax_drift is not None:
        ax_drift.set(title="model drift: EWMA relative error per truth "
                           "source (red = tolerance breach)",
                     xlabel="step")
        if ax_drift.lines:
            ax_drift.set_yscale("log")  # errors span orders of magnitude
        ax_breach.set(title="cumulative drift breaches", xlabel="step")
        all_axes += [ax_drift, ax_breach]
    for ax in all_axes:
        ax.grid(True, alpha=0.3)
        if ax.lines or ax.patches or ax.collections:
            ax.legend(fontsize=8)
    fig.tight_layout()
    fig.savefig(out, dpi=120)
    if show:
        plt.show()
    plt.close(fig)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("paths", nargs="+",
                   help="run directories or .jsonl files to plot together")
    p.add_argument("-o", "--out", default="history.png")
    p.add_argument("--smooth", type=int, default=1,
                   help="moving-average window over train-series points")
    p.add_argument("--show", action="store_true")
    args = p.parse_args(argv)
    runs = discover(args.paths)
    out = plot(runs, args.out, show=args.show, smooth=args.smooth)
    print(f"wrote {out} ({len(runs)} run{'s' if len(runs) != 1 else ''})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
