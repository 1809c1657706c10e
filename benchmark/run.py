"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic files by name, builds the system
under test on the chip, warms up the cell's own shapes, measures for
``--seconds``, reads the device's peak memory, frees the program's state,
lets the plain reference decide ``correct``, and prints one JSON line. With
``--trace 1`` the first seconds of the window run under the profiler and the
line carries the per-layer metrics; otherwise the end-to-end ones.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import registry, window  # noqa: E402
from benchmark.harness.peaks import peaks_for  # noqa: E402
from benchmark.trace import reduce as trace_reduce  # noqa: E402


def run_cell(cell: dict, bench: dict, seed: int, seconds: float, trace: bool,
             devices: list, rate_scale: float = 1.0, t_start: float = None,
             tamper=None) -> dict:
    """One run of one cell on ``devices``; returns the result line's
    object. ``tamper(driver)`` lets a test break the timed path."""
    t_start = T_START if t_start is None else t_start
    counter = window.CompileCounter()
    driver = registry.load_driver(cell).Driver(cell, seed, devices)
    setup = driver.setup(tamper)
    requests, hits = counter.snapshot()
    traced = window.TracedPart(trace, cell["name"])
    setup_s = time.perf_counter() - t_start
    rec = driver.window(seconds, traced, rate_scale)
    in_window = counter.snapshot()[0] - requests
    values = dict(driver.end_to_end(rec), setup_s=setup_s)
    device = window.device_record(devices)
    summary = driver.summary(rec)
    sampled = driver.sample(rec)
    attempted, failed = driver.attempted_failed(rec)
    driver.release()
    t_check = time.perf_counter()
    correct, compared = driver.check(rec, sampled)
    print(f"seconds: set-up {setup_s:.1f}, window and drain "
          f"{t_check - t_start - setup_s:.1f}, reference "
          f"{time.perf_counter() - t_check:.1f}", file=sys.stderr)
    window.print_compared(compared, correct)

    breakdown = None
    if trace:
        path = traced.xplane()
        if path is None:
            raise RuntimeError("the profiler wrote no xplane file")
        tsum = trace_reduce.reduce_events(trace_reduce.load_events(path),
                                          summary.get("scopes"))
        traced.discard()
        device["busy_s"] = tsum["busy_s"]
        device["window_s"] = tsum["window_s"]
        breakdown = trace_reduce.breakdown(tsum)
        run = {"cell": cell, "summary": summary, "model": driver.model,
               "trace": tsum,
               "values": values, "device": device,
               "peaks": peaks_for(devices[0].device_kind) if
               devices[0].platform != "cpu" else None,
               "setup": {"warmup_s": setup["warmup_s"],
                         "compile_requests": requests, "cache_hits": hits,
                         "compiles_in_window": in_window}}
        metrics = {}
        for m in registry.metrics_for(bench, "per_layer", cell["name"]):
            value = registry.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = (value, m["unit"])
    else:
        metrics = {m["name"]: (values[m["name"]], m["unit"])
                   for m in registry.metrics_for(bench, "end_to_end",
                                                 cell["name"])}
    return {"line": window.last_line(correct, attempted, failed, metrics,
                                     device, compared, breakdown),
            "correct": correct, "compared": compared, "metrics": metrics}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate-scale", type=float, default=1.0,
                    help="multiplies an open loop's rate (the knee sweep)")
    args = ap.parse_args()
    bench = registry.load_benchmark()
    cell = registry.load_cell(bench, args.workload)

    from deeplearning4j_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    import jax

    # keep every program, wherever the cache was placed: most of a cell's
    # programs compile in under JAX's one-second threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = window.require_devices(cell["chips"])
    out = run_cell(cell, bench, args.seed, args.seconds, bool(args.trace),
                   devices, args.rate_scale)
    print(out["line"], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
