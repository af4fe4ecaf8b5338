#!/usr/bin/env python
"""fluidlint — static program verifier CLI.

Runs the analysis/ pass pipeline (shape/dtype inference, structural
verification, TPU performance lints) over a program WITHOUT tracing,
jitting, or touching any accelerator, and prints the diagnostics.

Targets (one of):
  --model NAME       build a model-zoo program (paddle_tpu/models/zoo.py)
  --all-models       lint EVERY zoo model in this one process and emit
                     a single summary (one JSON document with --json) —
                     the CI sweep, replacing N separate invocations
  --program FILE     a Program saved as JSON (Program.to_json), with
                     optional --startup FILE and --fetch NAME ...
  --saved-model DIR  a save_inference_model directory (__model__.json +
                     __meta__.json supply the program and fetch names)
  --list             print the zoo model names and exit

Output: human-readable diagnostics, or one JSON document with --json
(for CI — tools/selfcheck.sh). Exit code 1 iff any error-level
diagnostic was found, else 0; warnings never fail the lint.

--report additionally prints the static cost/memory analysis
(analysis/cost.py): the top-k costliest ops by FLOPs, total
FLOPs/bytes, the liveness-based peak-residency estimate, the fwd→bwd
residual estimate with the recommended remat policy, the DCE-provable
dead-op count, the rewrite-pipeline stats (Program.optimize on a
throwaway clone: ops folded, chains fused, merged/removed, with
per-pass cost-model FLOPs/bytes deltas), and the numerics analysis
(analysis/numcheck.py: CODES findings + finiteness verdict, under
"report.numerics" with --json; tools/numlint.py is the gating CLI).
--all-models also aggregates the numerics codes per model, and a
builder-side numerics ERROR fails the sweep like a verifier error. The cost analysis never
traces or compiles; the rewrite stats' fold pass evaluates constant
ops eagerly on host CPU (JAX_PLATFORMS=cpu is pinned). --json always
carries the lowering↔infer registry coverage ("infer_coverage") and,
with --report, the full cost document under "report" (rewrite stats
under "report.rewrites").

Examples:
  python tools/fluidlint.py --model mnist
  python tools/fluidlint.py --model llama --json
  python tools/fluidlint.py --model resnet --report
  python tools/fluidlint.py --saved-model /tmp/my_model --json
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the verifier never compiles anything; pin jax to host CPU before any
# backend can initialize so the lint never takes the chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _load_target(args):
    """Returns (main, startup|None, fetch_list|None, feed_names|None,
    label)."""
    from paddle_tpu.core.executor import force_cpu
    # racecheck: ok(global-mutation) — lint CLI entrypoint: pins the
    # backend before anything compiles, single-threaded process
    force_cpu()
    if args.model:
        from paddle_tpu.models.zoo import build_zoo_program
        zp = build_zoo_program(args.model)
        return (zp.main, zp.startup, zp.fetch_list, zp.feed_names,
                f"model:{args.model}")
    from paddle_tpu.core.framework import Program
    if args.saved_model:
        with open(os.path.join(args.saved_model, "__model__.json")) as f:
            main = Program.from_json(f.read())
        meta_path = os.path.join(args.saved_model, "__meta__.json")
        fetch, feed = None, None
        if os.path.exists(meta_path):
            with open(meta_path) as f:
                meta = json.load(f)
            fetch = meta.get("fetch_names")
            feed = meta.get("feed_names")
        return main, None, fetch, feed, f"saved:{args.saved_model}"
    with open(args.program) as f:
        main = Program.from_json(f.read())
    startup = None
    if args.startup:
        with open(args.startup) as f:
            startup = Program.from_json(f.read())
    fetch = args.fetch or None
    return main, startup, fetch, None, f"program:{args.program}"


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="fluidlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    target = ap.add_mutually_exclusive_group(required=True)
    target.add_argument("--model", help="model-zoo entry to build")
    target.add_argument("--all-models", action="store_true",
                        help="lint the whole zoo in one process")
    target.add_argument("--program", help="Program JSON file")
    target.add_argument("--saved-model",
                        help="save_inference_model directory")
    target.add_argument("--list", action="store_true",
                        help="list zoo model names and exit")
    ap.add_argument("--startup", help="startup Program JSON "
                                      "(with --program)")
    ap.add_argument("--fetch", nargs="*", default=None,
                    help="fetch target names (with --program)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output for CI")
    ap.add_argument("--no-warnings", action="store_true",
                    help="print errors only")
    ap.add_argument("--report", action="store_true",
                    help="static cost/memory report (top-k op costs, "
                         "peak residency, dead-op count, remat "
                         "recommendation)")
    ap.add_argument("--top-k", type=int, default=10,
                    help="ops listed in the --report cost table")
    ap.add_argument("--assume-batch", type=int, default=1,
                    help="value substituted for unknown (-1) dims in "
                         "the cost model")
    args = ap.parse_args(argv)

    if args.list:
        from paddle_tpu.models.zoo import zoo_model_names
        print("\n".join(zoo_model_names()))
        return 0

    if args.all_models:
        return _lint_all_models(args)

    main_prog, startup, fetch, feed_names, label = _load_target(args)
    from paddle_tpu.analysis import CODES, errors, verify_program
    diags = verify_program(main_prog, startup=startup, fetch_list=fetch,
                           feed_names=feed_names, level="full")
    errs = errors(diags)
    warns = [d for d in diags if d.level == "warning"]

    report = None
    rewrites = None
    layout_plan = None
    numerics = None
    if args.report:
        from paddle_tpu.analysis import program_cost
        report = program_cost(main_prog, fetch_list=fetch,
                              assume_batch=args.assume_batch)
        rewrites = _rewrite_stats(main_prog, fetch)
        layout_plan = _layout_stats(main_prog, fetch,
                                    args.assume_batch)
        numerics = _numerics_stats(main_prog, fetch)

    if args.as_json:
        from paddle_tpu.core.registry import (registered_infer_types,
                                              registered_op_types)
        lowering = registered_op_types()
        infer = set(registered_infer_types())
        doc = {
            "target": label,
            "n_errors": len(errs),
            "n_warnings": len(warns),
            "codes": sorted({d.code for d in diags}),
            "diagnostics": [d.to_dict() for d in diags],
            "infer_coverage": {
                "n_lowering": len(lowering),
                "n_infer": len(infer),
                "missing": [t for t in lowering if t not in infer],
            },
        }
        if report is not None:
            doc["report"] = report.to_dict(args.top_k)
            doc["report"]["rewrites"] = rewrites
            doc["report"]["layout"] = layout_plan
            doc["report"]["numerics"] = numerics
        print(json.dumps(doc, indent=2))
    else:
        shown = errs if args.no_warnings else diags
        for d in shown:
            print(d.format())
        print(f"\n{label}: {len(errs)} error(s), {len(warns)} "
              f"warning(s)")
        if report is not None:
            _print_report(label, report, args.top_k)
            _print_rewrites(rewrites)
            _print_layout(layout_plan)
            _print_numerics(numerics)
        unknown = {d.code for d in diags} - set(CODES)
        if unknown:
            print(f"note: undocumented codes emitted: {unknown}",
                  file=sys.stderr)
    return 1 if errs else 0


def _lint_all_models(args):
    """One process, every zoo model: build → verify, one aggregated
    document. Builders and the verifier are jax-free, so the sweep is
    pure host work no matter how big the zoo grows."""
    from paddle_tpu.core.executor import force_cpu
    # racecheck: ok(global-mutation) — same CLI entrypoint contract
    force_cpu()
    from paddle_tpu.analysis import check_program, errors, verify_program
    from paddle_tpu.models.zoo import build_zoo_program, zoo_model_names
    models = {}
    total_errs = 0
    for name in zoo_model_names():
        try:
            zp = build_zoo_program(name)
            diags = verify_program(
                zp.main, startup=zp.startup, fetch_list=zp.fetch_list,
                feed_names=zp.feed_names, level="full")
            num = check_program(zp.main, fetch_list=zp.fetch_list)
        except Exception as e:      # a builder crash IS a lint failure
            models[name] = {"build_error": repr(e), "n_errors": 1,
                            "n_warnings": 0, "codes": [],
                            "diagnostics": []}
            total_errs += 1
            continue
        errs = errors(diags)
        # a builder-side numerics ERROR fails the sweep the same way a
        # verifier error does (numlint gates fixtures; this gates the
        # zoo builders themselves)
        total_errs += len(errs) + len(num.errors())
        models[name] = {
            "n_errors": len(errs),
            "n_warnings": sum(d.level == "warning" for d in diags),
            "codes": sorted({d.code for d in diags}),
            "diagnostics": [d.to_dict() for d in diags],
            "numerics": {
                "n_errors": len(num.errors()),
                "n_warnings": len(num.warnings()),
                "codes": sorted({d.code for d in num.findings}),
                "finite_safe": num.finite_safe,
            },
        }
    if args.as_json:
        print(json.dumps({"target": "all-models",
                          "n_models": len(models),
                          "n_errors": total_errs,
                          "models": models}, indent=2))
    else:
        for name, doc in models.items():
            num = doc.get("numerics")
            status = doc.get("build_error") or (
                f"{doc['n_errors']} error(s), "
                f"{doc['n_warnings']} warning(s); numerics "
                f"{num['n_errors']}E/{num['n_warnings']}W"
                + (" finite-safe" if num["finite_safe"] else ""))
            print(f"{name:24s} {status}")
        print(f"\nall-models: {len(models)} model(s), "
              f"{total_errs} error(s)")
    return 1 if total_errs else 0


def _rewrite_stats(main_prog, fetch):
    """What the rewrite pipeline (Program.optimize) would do to this
    program, measured on a throwaway clone with per-pass cost-model
    deltas — ops folded, chains fused, merged/removed counts, and the
    estimated FLOPs/bytes movement per pass. None without a fetch
    contract (nothing is provably rewritable), and never touches the
    caller's program. NOTE: the fold pass evaluates lowering rules
    eagerly (jax on host CPU — JAX_PLATFORMS=cpu is pinned above);
    every other fluidlint path stays jax-free."""
    if not fetch:
        return None
    fetch_names = [v.name if hasattr(v, "name") else v
                   for v in fetch]
    try:
        clone = main_prog.clone(for_test=main_prog._is_test)
        report = clone.optimize(fetch_list=fetch_names,
                                collect_cost=True)
    except Exception as e:
        return {"error": repr(e)}
    doc = report.to_dict()
    doc["n_ops_before"] = len(main_prog.global_block().ops)
    doc["n_ops_after"] = len(clone.global_block().ops)
    return doc


def _layout_stats(main_prog, fetch, assume_batch):
    """What the opt-in layout pass (analysis/layout.py) would do:
    conversion regions, inserted-transpose count, and the cost model's
    estimated bytes delta. Pure analysis on the caller's program —
    nothing is mutated, nothing traced."""
    try:
        from paddle_tpu.analysis import analyze_layout
        fetch_names = [v.name if hasattr(v, "name") else v
                       for v in (fetch or [])] or None
        plan = analyze_layout(main_prog, fetch_list=fetch_names,
                              assume_batch=assume_batch)
        return plan.to_dict()
    except Exception as e:
        return {"error": repr(e)}


def _numerics_stats(main_prog, fetch):
    """The abstract numerics interpretation (analysis/numcheck.py):
    CODES findings, finiteness verdict, and the AMP bf16-narrowing
    count. Pure analysis — nothing mutated, nothing traced."""
    try:
        from paddle_tpu.analysis import check_program
        report = check_program(main_prog, fetch_list=fetch)
        return report.to_dict()
    except Exception as e:
        return {"error": repr(e)}


def _print_numerics(num):
    print("\n-- numerics analysis (numcheck; tools/numlint.py is the "
          "gate CLI) --")
    if num is None or "error" in num:
        print(f"numerics analysis failed: {num and num.get('error')}")
        return
    safe = "finite-safe" if num["finite_safe"] else "not finite-safe"
    print(f"{num['n_errors']} error(s), {num['n_warnings']} "
          f"warning(s); {safe}"
          + (f"; AMP={num['amp']}: {num['n_narrowed']} binding(s) "
             f"bf16-narrowed" if num["amp"] else ""))
    for d in num["findings"]:
        loc = f"b{d['block_idx']}#{d['op_idx']}" \
            if d.get("op_idx") is not None else "program"
        print(f"  {d['level']}[{d['code']}] {loc}: {d['message']}")


def _print_layout(plan):
    print("\n-- layout analysis (opt-in passes=('layout',...)) --")
    if plan is None or "error" in plan:
        print(f"layout analysis failed: {plan and plan.get('error')}")
        return
    if plan.get("refused"):
        print(f"whole-program refusal: {plan['refused']}")
        return
    if not plan["n_regions"]:
        print("no 4-D NCHW conv/pool/BN regions found")
        return
    print(f"{plan['n_regions']} region(s), {plan['n_selected']} "
          f"profitable; converting would insert "
          f"{plan['n_transposes']} frontier transpose(s) and save an "
          f"estimated {plan['bytes_delta']:.3g} B of implicit "
          f"relayout copies per step")
    for i, r in enumerate(plan["regions"]):
        verdict = "CONVERT" if r["selected"] else \
            f"keep NCHW ({r['reason']})"
        delta = r["bytes_delta"]
        print(f"  region {i}: {r['n_ops']} ops "
              f"({r['n_sensitive']} layout-sensitive), "
              f"{r['n_transposes']} frontier transpose(s), "
              f"est. delta {delta if delta is None else f'{delta:.3g}'}"
              f" B -> {verdict}")


def _print_rewrites(rw):
    print("\n-- rewrite pipeline (Program.optimize, on a clone) --")
    if rw is None:
        print("no fetch contract: nothing provably rewritable")
        return
    if "error" in rw:
        print(f"rewrite pipeline failed: {rw['error']}")
        return
    print(f"passes {','.join(rw['passes'])}: ops "
          f"{rw['n_ops_before']} -> {rw['n_ops_after']} "
          f"({rw['folded']} folded, {rw['fused']} chains fused, "
          f"{rw['merged']} merged, {rw['removed']} removed)")
    for name, d in (rw.get("cost_deltas") or {}).items():
        print(f"  {name:5s} est. delta: {d['flops']:+.3g} FLOPs  "
              f"{d['bytes']:+.3g} B  {d['n_ops']:+d} ops")


def _print_report(label, report, top_k):
    def _mb(b):
        return f"{b / 2**20:8.2f} MiB" if b is not None else "   n/a"

    print(f"\n-- static cost report ({label}, assumed batch "
          f"{report.assume_batch}) --")
    print(f"ops: {len(report.per_op)}  total FLOPs: "
          f"{report.total_flops:.3g}  total bytes: "
          f"{report.total_bytes:.3g}  ops w/ unknown shapes: "
          f"{report.n_unknown_shape_ops}")
    print(f"params resident: {_mb(report.params_bytes)}   "
          f"peak residency estimate: {_mb(report.peak_residency_bytes)}")
    if report.residual_at_backward_bytes is not None:
        print(f"fwd->bwd residual estimate: "
              f"{_mb(report.residual_at_backward_bytes)}   recommended "
              f"remat policy: {report.recommended_remat_policy!r}")
    if report.dead_op_count is not None:
        print(f"DCE-provable dead ops: {report.dead_op_count}")
    print(f"top {top_k} ops by FLOPs:")
    for c in report.top_ops(top_k):
        outs = ",".join(c.outputs)
        print(f"  {c.flops:12.3g} flops {c.bytes:12.3g} B  "
              f"b{c.block_idx}#{c.op_idx:<4} {c.op_type:24s} -> {outs}")


if __name__ == "__main__":
    sys.exit(main())
