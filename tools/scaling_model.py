"""Analytic 8->256-chip scaling model for the BASELINE configs.

Round-3 verdict item 3: the CPU-mesh fixed-work audit (SCALING.json)
bounds the framework's partition overhead, but says nothing about real
ICI/DCN time at pod scale. This model predicts it from first principles
so the 256-chip claim is FALSIFIABLE: every input is either a
single-chip throughput (STALE: see MODELS below), a public spec
(bandwidths), or a stated assumption — change any input and the table
recomputes (`python tools/scaling_model.py` writes SCALING_MODEL.json;
prose + derivation in SCALING_MODEL.md).

Model (per training step, per chip):

  t_comp(b)   = b / img_s_1chip            -- measured, assumes the
                                              single-chip MFU holds at
                                              the per-chip batch (A1)
  ring(S, n, BW) = 2 * (n-1)/n * S / BW    -- bandwidth term of a ring
                                              allreduce moving S wire
                                              bytes/chip (reduce-scatter
                                              + allgather); latency
                                              ignored (A2)
  hierarchical(S, k, s) = ring(S, k, ICI) + ring(S/k, s, DCN)
                                           -- k chips/slice, s slices:
                                              in-slice phase on ICI,
                                              cross-slice phase on the
                                              1/k shard over DCN

  BSP:   t_step = t_comp + (1 - h) * t_sync        (h = overlap, A3)
  EASGD: t_step = t_comp + (1-h) * ring(S_param, n_w, BW_worker)/avg_freq
         (elastic exchange = one psum of param-sized diffs over the
          worker axis every avg_freq steps; group-internal grad psum
          charged like BSP over the group)
  GoSGD: t_step = t_comp + (1-h) * p_push * 2 * S_param / BW_worker
         (one ppermute send+recv of params, Bernoulli p per step)

  efficiency(n) = t_comp / t_step          -- vs ideal linear scaling

Assumptions (stated; the table prints which bind):
  A1 fixed per-chip batch (weak scaling) at the measured MFU.
  A2 ring latency + XLA scheduling gaps ignored -> optimistic for tiny
     messages; S here is 10^7..10^8 B, bandwidth-dominated.
  A3 overlap h: XLA overlaps collectives with independent backward
     compute. Reported at h=0 (worst case) and h=0.7 (typical measured
     overlap for conv nets; assumption, not a repo measurement).
  A4 v5e bandwidths: ICI 1600 Gbit/s/chip aggregate (public spec sheet)
     -> ~90 GB/s usable one-direction after protocol overhead
     (assumption); DCN 200 Gbit/s NIC per 8-chip host -> 3.1 GB/s/chip.
  A5 256 chips = one v5e pod (single ICI domain; 16x16 torus). The
     multi-slice rows model the same count as 4 slices x 64 chips.
"""

from __future__ import annotations

import json
import os

GB = 1e9
# -- inputs ---------------------------------------------------------------
BW_ICI = 90 * GB      # usable one-direction ICI B/s per chip (A4)
BW_DCN = 3.1 * GB     # usable DCN B/s per chip (A4)
OVERLAPS = (0.0, 0.7)  # A3

# single-chip throughput (img/s) and the per-chip batch each config
# trains (reference configs). STALE INPUTS: these four img_s values were
# taken on a development backend that no longer exists and their record
# is deleted; on the current machine they are not measured. Replace them
# from the first BENCHMARK.json cells before quoting a prediction.
MODELS = {
    # name: (img_s_1chip at its bench batch, params, per-chip batch)
    "alexnet": dict(img_s=18605.0, params=61e6, b=128),     # config #2
    "googlenet": dict(img_s=5268.9, params=7.0e6, b=32),    # config #3 (32 wkr x 32 = 1024 global)
    "resnet50": dict(img_s=2397.9, params=25.5e6, b=16),    # config #4 (256 per 16-chip worker)
    "vgg16": dict(img_s=1292.9, params=138e6, b=16),        # config #5 (64 wkr; 16/chip keeps HBM)
}


def ring(S, n, bw):
    return 0.0 if n <= 1 else 2.0 * (n - 1) / n * S / bw


def bsp_eff(model, n, wire_bytes, h, k_slice=None):
    m = MODELS[model]
    t_comp = m["b"] / m["img_s"]
    S = wire_bytes * m["params"]
    if k_slice and n > k_slice:  # hierarchical: k chips/slice over ICI, rest over DCN
        s = n // k_slice
        t_sync = ring(S, k_slice, BW_ICI) + ring(S / k_slice, s, BW_DCN)
    else:
        t_sync = ring(S, n, BW_ICI)
    return t_comp / (t_comp + (1 - h) * t_sync)


def easgd_eff(model, n_workers, group, avg_freq, h, workers_over_dcn):
    m = MODELS[model]
    t_comp = m["b"] / m["img_s"]
    S_grad = 4.0 * m["params"]          # fp32 grad psum inside the group
    S_param = 4.0 * m["params"]         # param-sized elastic diffs
    t_group = ring(S_grad, group, BW_ICI)          # every step
    bw_w = BW_DCN if workers_over_dcn else BW_ICI
    t_elastic = ring(S_param, n_workers, bw_w) / avg_freq
    return t_comp / (t_comp + (1 - h) * (t_group + t_elastic))


def gosgd_eff(model, n_workers, p_push, h, workers_over_dcn):
    m = MODELS[model]
    t_comp = m["b"] / m["img_s"]
    S_param = 4.0 * m["params"]
    bw_w = BW_DCN if workers_over_dcn else BW_ICI
    t_gossip = p_push * 2.0 * S_param / bw_w  # isend + irecv per pushing step
    return t_comp / (t_comp + (1 - h) * t_gossip)


def build_table():
    rows = []

    def add(config, n, detail, eff_by_h):
        rows.append({
            "config": config, "n_chips": n, "detail": detail,
            **{f"eff_h{int(h*100)}": round(e, 4) for h, e in eff_by_h.items()},
        })

    for wire, wname in ((4.0, "fp32"), (2.0, "bf16-wire"), (1.0, "int8-wire")):
        for n in (8, 64, 256):
            add("#2 alexnet BSP", n, f"single slice, {wname} ring",
                {h: bsp_eff("alexnet", n, wire, h) for h in OVERLAPS})
        add("#2 alexnet BSP", 256, f"4 slices x 64, {wname}",
            {h: bsp_eff("alexnet", 256, wire, h, k_slice=64) for h in OVERLAPS})

    for n in (32, 256):
        add("#3 googlenet BSP", n, "single slice, fp32 ring",
            {h: bsp_eff("googlenet", n, 4.0, h) for h in OVERLAPS})
    add("#3 googlenet BSP", 256, "4 slices x 64, fp32",
        {h: bsp_eff("googlenet", 256, 4.0, h, k_slice=64) for h in OVERLAPS})

    # config #4: 16 workers x 16 chips; workers across slices (DCN) vs
    # one pod (ICI); avg_freq=8 (reference-style)
    for dcn in (False, True):
        add("#4 resnet50 EASGD 16x16", 256,
            f"groups on ICI, workers over {'DCN' if dcn else 'ICI'}, avg_freq=8",
            {h: easgd_eff("resnet50", 16, 16, 8, h, dcn) for h in OVERLAPS})

    # config #5: 64 gossip workers (4 chips/worker at 256); p=1/avg_freq=0.125
    for dcn in (False, True):
        add("#5 vgg16 GoSGD 64", 256,
            f"p_push=0.125, peers over {'DCN' if dcn else 'ICI'}",
            {h: gosgd_eff("vgg16", 64, 0.125, h, dcn) for h in OVERLAPS})
    return rows


def main():
    table = build_table()
    out = {
        "inputs": {
            "BW_ICI_GBps": BW_ICI / GB, "BW_DCN_GBps": BW_DCN / GB,
            "overlaps": OVERLAPS, "models": MODELS,
        },
        "assumptions": ["A1 weak scaling at measured single-chip MFU",
                        "A2 bandwidth-only ring (latency ignored)",
                        "A3 overlap h in {0, 0.7}",
                        "A4 v5e: ICI 90 GB/s usable, DCN 3.1 GB/s/chip",
                        "A5 256 chips = one pod; multi-slice rows = 4x64"],
        "table": table,
    }
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "SCALING_MODEL.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    for r in table:
        print(json.dumps(r))
    print(json.dumps({"wrote": path}))


if __name__ == "__main__":
    main()
