"""One cell, once, in one process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration under configs/, its
traffic or job under traffic/ and each metric's reader under metrics/, all
by name. Set-up (weights, compile or cache read, warm-up) is timed from the
start of the process; then the builder measures for ``--seconds``. The last
line of standard output is the result; every line before it is for a
reader. Without the chips the cell asks for it exits 1 and prints no result.
"""
import time
T_PROCESS = time.monotonic()

import argparse            # noqa: E402
import importlib           # noqa: E402
import importlib.util      # noqa: E402
import json                # noqa: E402
import os                  # noqa: E402
import sys                 # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def find_cell(benchmark, workload):
    for cell in benchmark["workloads"]:
        if cell["name"] == workload:
            config = next(c for c in benchmark["configs"]
                          if c["name"] == cell["config"])
            return cell, config
    raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")


def metric_reader(name):
    """The reader of a metric: metrics/<name>.py, or for ``base.suffix``
    metrics/<base>.py. A reader is ``read(run) -> number or None``."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(HERE, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "benchmark.metrics." + stem.replace(".", "_"), path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module.read
    raise SystemExit(f"run.py: no reader metrics/{name}.py")


def metrics_of(benchmark, group, workload):
    return [m for m in benchmark[group]
            if "workloads" not in m or workload in m["workloads"]]


def device_report(jax, chips):
    """JAX's own account of the device, or exit 1: no chip, no result."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < chips:
        print(f"run.py: the cell needs {chips} TPU chip(s); JAX reports "
              f"{len(devices)} x {dev.platform!r}", file=sys.stderr)
        raise SystemExit(1)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak_bytes(jax, chips):
    """The allocator's peak_bytes_in_use on the fullest of the cell's
    devices, as JAX reports it."""
    stats = [d.memory_stats() for d in jax.devices()[:chips]]
    print("device memory as JAX reports it:",
          [{k: s[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                              "bytes_limit") if k in s} for s in stats],
          flush=True)
    return int(max(s["peak_bytes_in_use"] for s in stats))


def result_line(run, benchmark, cell, trace):
    """The contract's last line from the run's records."""
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(benchmark, group, cell["name"]):
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    line = {"correct": not run["problems"], "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics,
            "device": run["device"]}
    if trace and run.get("trace"):
        from benchmark import trace_reduce
        line["device"]["busy_s"] = run["trace"]["busy_s"]
        line["device"]["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = trace_reduce.breakdown(run["trace"])
    return line


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    cell, config_entry = find_cell(benchmark, args.workload)
    with open(os.path.join(ROOT, config_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")

    import jax
    import paddle_tpu as fluid
    from benchmark.tracing import Tracer
    device = device_report(jax, cell["chips"])
    peaks = load_json("peaks.json")
    if device["kind"] not in peaks:
        raise SystemExit(f"run.py: no peaks for {device['kind']!r} in "
                         "peaks.json; a device that is not there is an "
                         "error, not a default")
    print(f"cell {cell['name']}: config {cell['config']}, traffic "
          f"{cell['traffic']}, seed {args.seed}, {args.seconds} s, trace "
          f"{args.trace}; jax {jax.__version__} on {device}; compile cache "
          f"{fluid.enable_compile_cache()}", flush=True)

    builder = importlib.import_module(
        "benchmark.builders." + config["builder"]["kind"])
    system = builder.set_up(config, traffic, args.seed)
    setup_s = time.monotonic() - T_PROCESS
    print(f"set-up {setup_s:.3f} s", flush=True)
    tracer = Tracer(args.trace)
    try:
        run = builder.measure(system, traffic, args.seconds, args.seed,
                              tracer)
    finally:
        system.close()
    run.update(setup_s=setup_s, config=config, traffic=traffic,
               peaks=peaks[device["kind"]], chips=cell["chips"],
               trace=tracer.reduce(cell["chips"]))
    # Two sources, kept apart: the allocator's counter (peak_hbm_gb.*) and,
    # for a training cell, XLA's footprint of the step that ran
    # (step_footprint_gb). The device's memory_peak_bytes, which the
    # driver's size check reads, is the larger: this installation's
    # allocator leaves a running program's temporaries out of its counter
    # (README.md, "Memory").
    run["allocator_peak_bytes"] = memory_peak_bytes(jax, cell["chips"])
    device["memory_peak_bytes"] = max(
        run["allocator_peak_bytes"], run.get("step_footprint_bytes") or 0)
    print("memory: allocator peak %d bytes, step footprint %s bytes" % (
        run["allocator_peak_bytes"], run.get("step_footprint_bytes")),
        flush=True)
    run["device"] = device
    if args.trace and run["trace"] is None:
        run["problems"].append("the traced run holds no device operation")
    for p in run["problems"]:
        print("PROBLEM:", p, flush=True)
    print(json.dumps(result_line(run, benchmark, cell, args.trace)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
