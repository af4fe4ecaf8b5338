"""Find a serving cell's knee again: one engine, a ladder of rates.

    python3 benchmark/sweep.py --workload mistral7b-serve-chat \\
        --rates 2.5,3,3.5,4,4.5 --seconds 30 --seed 1

Runs the cell's own traffic file at each rate in turn (lead-in, window,
drain) in one process on the chip and prints one JSON line a rate. The knee
is the highest rate at which the backlog does not grow over the run: the
queue is empty at the window's end as at its start, and the second half's
median time to first token is that of the first half. The cell's
``rate_rps`` is then 0.8 of it, written into the traffic file by hand with
these lines beside it. Not part of a check; never run by the driver.
"""
import argparse
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from benchmark import run as R
    from benchmark.builders import serve
    from benchmark.loadgen import percentile
    from benchmark.metrics import _requests
    from benchmark.tracing import Tracer
    import jax
    import paddle_tpu as fluid

    with open(os.path.join(R.ROOT, "BENCHMARK.json")) as f:
        cell, entry = R.find_cell(json.load(f), args.workload)
    with open(os.path.join(R.ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = R.load_json("traffic", cell["traffic"] + ".json")
    if traffic["loop"] != "open":
        raise SystemExit("sweep.py: only an open loop has a rate to sweep")
    print("device", R.device_report(jax, cell["chips"]), "compile cache",
          fluid.enable_compile_cache(), flush=True)
    system = serve.set_up(config, traffic, args.seed)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            t = copy.deepcopy(traffic)
            t["rate_rps"] = rate
            run = serve.measure(system, t, args.seconds, args.seed,
                                Tracer(False))
            mid = run["t0"] + args.seconds / 2
            halves = [[], []]
            for r in _requests.sampled(run):
                if r["first_token"] is not None:
                    halves[r["due"] >= mid].append(
                        1e3 * (r["first_token"] - r["due"]))
            e = run["edges"]
            print(json.dumps({
                "rate_rps": rate, "offered": run["attempted"],
                "failed": run["failed"],
                "queue_at_edges": [e["start"]["queue_depth"],
                                   e["end"]["queue_depth"]],
                "slots_at_edges": [e["start"]["active_slots"],
                                   e["end"]["active_slots"]],
                "ttft_p50_ms_halves": [percentile(h, 50) for h in halves],
                "ttft_p90_ms": percentile(_requests.ttft_ms(run), 90),
                "tpot_p90_ms": percentile(_requests.tpot_ms(run), 90),
                "out_tok_s": R.metric_reader("out_tok_s")(run),
                "batch_occupancy": R.metric_reader("batch_occupancy")(run),
                "problems": run["problems"][:3]}), flush=True)
    finally:
        system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
