"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Generic: knows no cell. Finds the cell in BENCHMARK.json, its workload and
configuration files by name, and hands over to ``drivers/<kind>.py``. Refuses
to measure off a TPU. The last line of standard output is the contract's one
JSON object; the process then leaves through ``os._exit``.
"""

import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]


def process_start():
    """perf_counter reading at which this process was created (interpreter
    start-up belongs to set-up)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        if 0 <= age < 60:
            return time.perf_counter() - age
    except (OSError, ValueError, IndexError):
        pass
    return _T_IMPORT


def main(argv=None):
    from harness import lastline, manifest, peaks

    t_start = process_start()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy sizes; can never report correct: true")
    args = ap.parse_args(argv)

    man, cell, workload, config = manifest.resolve(args.workload)
    seconds = float(man["run_seconds"]) if args.seconds is None else args.seconds
    try:
        import jax

        devices = jax.devices()
    except Exception as e:  # noqa: BLE001 - no backend at all: refuse, no result
        lastline.refuse(f"JAX found no device: {e!r}")
    if not args.tiny:
        if devices[0].platform != "tpu":
            lastline.refuse(f"platform is {devices[0].platform!r}, not 'tpu': no measurement off the chip")
        if len(devices) < int(cell["chips"]):
            lastline.refuse(f"{args.workload} needs {cell['chips']} chips, JAX sees {len(devices)}")
        peaks.peaks_for(devices[0].device_kind)
    driver = manifest.load_module("drivers", workload["driver"])
    if driver is None:
        lastline.refuse(f"no driver benchmark/drivers/{workload['driver']}.py")
    ctx = {"manifest": man, "cell": cell, "workload": workload, "config": config,
           "seed": args.seed, "seconds": seconds, "trace": bool(args.trace),
           "tiny": args.tiny, "t_process_start": t_start, "t_backend": time.perf_counter()}
    try:
        result = driver.run(ctx)
    except BaseException:  # noqa: BLE001 - any failure: trace it, no result line
        traceback.print_exc()
        lastline.refuse("the run failed; no result")
    line = lastline.build(result["correct"], result["attempted"], result["failed"],
                          result["metrics"], result["device"], result["checks"],
                          breakdown=result.get("breakdown"))
    lastline.emit(line, 0)


if __name__ == "__main__":
    main()
