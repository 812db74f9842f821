"""Tuned-vs-default speedups from the unified cross-backend autotuner.

    PYTHONPATH=src python benchmarks/bench_autotune.py [--steps 32]

For each problem: measure the old fixed default plan, run the autotuner
(first run = measured search over the pooled jnp + Pallas candidates,
logged; the winner lands in the plan cache keyed per-steps), measure the
tuned plan, and report the speedup.  A second ``tune`` call per problem
demonstrates the cache hit (no re-measurement).

Output rows: ``name,us_per_step,derived`` (derived = plan / speedup).
``--json PATH`` additionally records per-problem rows including the
static-audit overhead (``audit_seconds``) and how many candidates the
auditor pruned before measurement (``n_pruned_static``) — observability
only, never gating.
"""
import argparse
import json
import logging
import os
import sys
import tempfile

sys.path.insert(0, "src")

from repro.core import autotune          # noqa: E402
from repro.core.api import StencilProblem  # noqa: E402
from repro.core.timing import Row, bench, gflops  # noqa: E402

PROBLEMS = [
    ("1d3p", (1 << 16,)),
    ("2d5p", (512, 512)),
    ("3d7p", (32, 32, 64)),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--cache", default=None,
                    help="plan cache path (default: fresh temp file)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write per-problem rows (incl. audit_seconds, "
                         "n_pruned_static) as JSON")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()
    logging.basicConfig(level=logging.INFO,
                        format="%(name)s: %(message)s")
    cache = args.cache or os.path.join(tempfile.mkdtemp(), "plans.json")
    print(f"# plan cache: {cache}", file=sys.stderr)

    rows = []
    for name, shape in PROBLEMS:
        prob = StencilProblem(name, shape)
        tag = f"{name}@{'x'.join(map(str, shape))}"
        x = prob.init(0)
        flops = prob.model_flops(args.steps)

        t_def = bench(lambda: prob.run(x, args.steps, prob.default_plan()))
        res = autotune.tune(prob, steps=args.steps, cache_path=cache)
        if res.cached:      # user-supplied cache already holds this key
            print(f"# {tag}: plan already cached, skipping search",
                  file=sys.stderr)
        # identical plan → identical program; re-measuring only adds noise
        t_tuned = t_def if res.plan == prob.default_plan() \
            else bench(lambda: prob.run(x, args.steps, res.plan))

        res2 = autotune.tune(prob, steps=args.steps, cache_path=cache)
        assert res2.cached and res2.plan == res.plan, \
            "second tune call must be a cache hit with the same plan"

        print(Row(f"{tag}_default", t_def,
                  f"{gflops(flops, t_def):.2f}gflops"))
        print(Row(f"{tag}_tuned", t_tuned,
                  f"{res.plan.backend}/{res.plan.scheme}/k={res.plan.k}/"
                  f"{t_def / t_tuned:.2f}x"))
        print(f"# {tag}: tuned {t_def / t_tuned:.2f}x vs default "
              f"(winner backend={res.plan.backend}), "
              f"{res.n_measured}/{res.n_candidates} candidates measured, "
              f"{res.n_pruned_static} pruned statically "
              f"({res.audit_seconds * 1e3:.0f} ms audit), "
              f"second run cache-hit={res2.cached}", file=sys.stderr)
        if t_tuned > t_def * 1.05:
            print(f"# WARNING {tag}: tuned slower than default "
                  f"({t_tuned:.3e} vs {t_def:.3e})", file=sys.stderr)
        rows.append({
            "problem": tag, "steps": args.steps,
            "seconds_per_step_default": t_def,
            "seconds_per_step_tuned": t_tuned,
            "speedup": t_def / t_tuned,
            "plan": autotune.plan_to_dict(res.plan),
            "n_candidates": res.n_candidates,
            "n_measured": res.n_measured,
            "n_pruned_static": res.n_pruned_static,
            "audit_seconds": res.audit_seconds,
            "pruned": [{"plan": autotune.plan_to_dict(p),
                        "violations": sorted(set(v))}
                       for p, v in res.pruned],
            "cache_hit_second_run": bool(res2.cached),
        })
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": rows}, f, indent=1)
        print(f"# wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
