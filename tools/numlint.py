#!/usr/bin/env python
"""numlint — static numerics & precision-flow lint CLI (numcheck).

Runs the abstract numerics interpreter (analysis/numcheck.py) over a
program WITHOUT tracing or compiling anything and prints the CODES
findings: ``fp16-overflow-risk``, ``cast-precision-loss``,
``int8-scale-clip``, ``domain-hazard``, ``amp-unprotected-reduce``
(docs/RELIABILITY.md "Numerics checking").

Targets (one of):
  --model NAME       build a model-zoo program (paddle_tpu/models/zoo.py)
  --all-models       lint EVERY zoo model in this one process — the CI
                     sweep (one JSON document with --json)
  --program FILE     a Program saved as JSON (Program.to_json), with
                     optional --startup FILE and --fetch NAME ...
  --saved-model DIR  a save_inference_model directory
  --list             print the zoo model names and exit

--amp O1|O2 transpiles the target(s) to mixed precision first, so the
sweep covers the AMP dtype-narrowing flow the rewrite gates consult.

Suppression uses the same grammar as racecheck (analysis/suppress.py)
under the ``numcheck:`` tag::

    # numcheck: ok(<code>[, <code>...]) — <non-empty reason>

but matched FILE-SCOPED rather than line-anchored: numcheck findings
point at IR ops, not source lines, so a suppression anywhere in the
suppression source (default for model targets:
``paddle_tpu/models/zoo.py`` — the builders' home; override with
--suppressions FILE) suppresses that code for the target. Suppressed
findings are reported but do not fail the lint; a reason-less
``ok(...)`` is itself a ``bad-suppression`` warning.

Exit status is 1 iff any UNSUPPRESSED error-level finding exists (for
--all-models: in any model, and a builder crash counts) — the
selfcheck stage 11 gate.
"""
import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# numcheck never compiles anything; pin jax to host CPU before any
# backend can initialize so the lint never takes the chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_ZOO_SOURCE = os.path.join(_REPO, "paddle_tpu", "models", "zoo.py")


def _load_suppressions(path):
    from paddle_tpu.analysis.suppress import Suppressions
    if not path or not os.path.isfile(path):
        return None
    with open(path, "r", encoding="utf-8") as f:
        return Suppressions(f.read(), path, tag="numcheck")


def _lint_program(main, fetch, amp, supp):
    """Returns (doc, n_unsuppressed_errors). ``doc`` is the per-target
    JSON fragment: the NumericsReport dict with findings split into
    unsuppressed/suppressed by the file-scoped suppression table."""
    from paddle_tpu.analysis.numcheck import check_program
    if amp:
        from paddle_tpu.transpiler import amp_transpile
        amp_transpile(main, level=amp)
    report = check_program(main, fetch_list=fetch)
    findings, suppressed = [], []
    for d in report.findings:
        reason = supp.match_any(d.code) if supp is not None else None
        if reason is not None:
            suppressed.append((d, reason))
        else:
            findings.append(d)
    bad = list(supp.bad) if supp is not None else []
    n_err = sum(d.level == "error" for d in findings)
    doc = report.to_dict()
    doc["findings"] = [d.to_dict() for d in findings]
    doc["n_findings"] = len(findings)
    doc["n_errors"] = n_err
    doc["n_warnings"] = (sum(d.level == "warning" for d in findings)
                         + len(bad))
    doc["suppressed"] = [dict(d.to_dict(), reason=reason)
                         for d, reason in suppressed]
    doc["bad_suppressions"] = [d.to_dict() for d in bad]
    return doc, n_err


def _print_doc(label, doc, show_suppressed):
    for d in doc["findings"]:
        loc = f"b{d['block_idx']}#{d['op_idx']}" \
            if d.get("op_idx") is not None else "program"
        print(f"{d['level']}[{d['code']}] {label} {loc}: "
              f"{d['message']}")
        if d.get("hint"):
            print(f"    hint: {d['hint']}")
    for d in doc["bad_suppressions"]:
        print(f"{d['level']}[{d['code']}] {d['path']}:{d['line']}: "
              f"{d['message']}")
    if show_suppressed:
        for d in doc["suppressed"]:
            print(f"suppressed[{d['code']}] {label} — {d['reason']}")
    safe = "finite-safe" if doc["finite_safe"] else "not finite-safe"
    print(f"{label}: {doc['n_errors']} error(s), "
          f"{doc['n_warnings']} warning(s), "
          f"{len(doc['suppressed'])} suppressed; {safe}"
          + (f"; {doc['n_narrowed']} binding(s) bf16-narrowed"
             if doc["amp"] else ""))


def _load_explicit(args):
    from paddle_tpu.core.framework import Program
    if args.saved_model:
        with open(os.path.join(args.saved_model, "__model__.json")) as f:
            main = Program.from_json(f.read())
        meta_path = os.path.join(args.saved_model, "__meta__.json")
        fetch = None
        if os.path.exists(meta_path):
            with open(meta_path) as f:
                fetch = json.load(f).get("fetch_names")
        return main, fetch, f"saved:{args.saved_model}"
    with open(args.program) as f:
        main = Program.from_json(f.read())
    return main, args.fetch or None, f"program:{args.program}"


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="numlint", description=__doc__,
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
    ap.add_argument("--startup", help="ignored (accepted for symmetry "
                                      "with fluidlint)")
    ap.add_argument("--fetch", nargs="*", default=None,
                    help="fetch target names (with --program)")
    ap.add_argument("--amp", default=None, choices=("O1", "O2"),
                    help="transpile the target(s) to mixed precision "
                         "before checking")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output for CI")
    ap.add_argument("--suppressions", default=None,
                    help="source file carrying '# numcheck: ok(...)' "
                         "comments (default for model targets: the "
                         "zoo builder module; none otherwise)")
    ap.add_argument("--show-suppressed", action="store_true",
                    help="also print suppressed findings (text mode)")
    args = ap.parse_args(argv)

    if args.list:
        from paddle_tpu.models.zoo import zoo_model_names
        print("\n".join(zoo_model_names()))
        return 0

    from paddle_tpu.core.executor import force_cpu
    # racecheck: ok(global-mutation) — lint CLI entrypoint: pins the
    # backend before anything compiles, single-threaded process
    force_cpu()

    supp_path = args.suppressions
    if supp_path is None and (args.model or args.all_models):
        supp_path = _ZOO_SOURCE
    supp = _load_suppressions(supp_path)

    if args.all_models:
        from paddle_tpu.models.zoo import (build_zoo_program,
                                           zoo_model_names)
        models, total_errs = {}, 0
        for name in zoo_model_names():
            try:
                zp = build_zoo_program(name)
                doc, n_err = _lint_program(
                    zp.main, zp.fetch_list, args.amp, supp)
            except Exception as e:  # a builder crash IS a lint failure
                models[name] = {"build_error": repr(e), "n_errors": 1}
                total_errs += 1
                continue
            models[name] = doc
            total_errs += n_err
        if args.as_json:
            print(json.dumps({"target": "all-models",
                              "amp": args.amp or False,
                              "n_models": len(models),
                              "n_errors": total_errs,
                              "models": models}, indent=2))
        else:
            for name, doc in models.items():
                if "build_error" in doc:
                    print(f"{name:24s} BUILD ERROR: "
                          f"{doc['build_error']}")
                    continue
                safe = "finite-safe" if doc["finite_safe"] else \
                    "not finite-safe"
                print(f"{name:24s} {doc['n_errors']} error(s), "
                      f"{doc['n_warnings']} warning(s), "
                      f"{len(doc['suppressed'])} suppressed; {safe}")
            amp_tag = f" @ amp={args.amp}" if args.amp else ""
            print(f"\nall-models{amp_tag}: {len(models)} model(s), "
                  f"{total_errs} unsuppressed error(s)")
        return 1 if total_errs else 0

    if args.model:
        from paddle_tpu.models.zoo import build_zoo_program
        zp = build_zoo_program(args.model)
        main_prog, fetch, label = (zp.main, zp.fetch_list,
                                   f"model:{args.model}")
    else:
        main_prog, fetch, label = _load_explicit(args)

    doc, n_err = _lint_program(main_prog, fetch, args.amp, supp)
    doc["target"] = label
    if args.as_json:
        print(json.dumps(doc, indent=2))
    else:
        _print_doc(label, doc, args.show_suppressed)
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
