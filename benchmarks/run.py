"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; each module also emits
``<fig>/validate/...`` rows checking the paper's qualitative claims
against our implementation (EXPERIMENTS.md cross-references these).

Default profile is ``quick`` (scaled-down sizes, ~15 min CPU); pass
``--full`` for the paper-scale settings.  ``--repeats N`` overrides every
module's timing-loop repetition count (rows then report median + min;
gates compare medians — PR 1 measured ~2x wall-clock noise on this box).
``--json-out FILE`` additionally writes every emitted row as JSON so
benchmark runs can be committed / uploaded as ``BENCH_*.json`` artifacts
and tracked across PRs; an existing file is *merged into* (rows of
modules not re-run are kept), so multi-suite CI runs can share one
artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from repro.compile_cache import use_compile_cache

from . import (allpairs_throughput, common, construction_throughput,
               degraded_serving, fig3_synthetic_ip, fig4_binary,
               fig5_endbiased, fig6_join_corr, fig7_runtime, fig9_textsim,
               fig10_joinsize, matrix_product, merge_throughput,
               obs_overhead, sketchdp_dryrun, table2_realworld,
               topk_discovery)

MODULES = [
    ("fig3_synthetic_ip", fig3_synthetic_ip),
    ("fig4_binary", fig4_binary),
    ("fig5_endbiased", fig5_endbiased),
    ("fig6_join_corr", fig6_join_corr),
    ("fig7_runtime", fig7_runtime),
    ("table2_realworld", table2_realworld),
    ("fig9_textsim", fig9_textsim),
    ("fig10_joinsize", fig10_joinsize),
    ("sketchdp_dryrun", sketchdp_dryrun),
    ("allpairs_throughput", allpairs_throughput),
    ("topk_discovery", topk_discovery),
    ("construction_throughput", construction_throughput),
    ("merge_throughput", merge_throughput),
    ("matrix_product", matrix_product),
    ("degraded_serving", degraded_serving),
    ("obs_overhead", obs_overhead),
]


def _row_payload(module: str, row_name: str, us, derived: str,
                 profile: str) -> dict:
    # profile rides on every row: merged artifacts can mix quick/full runs
    # of different modules, so the top-level field alone would mislabel
    # preserved rows
    row = {"module": module, "name": row_name,
           "us_per_call": float(us), "derived": derived, "profile": profile}
    # time_callable returns a Timing carrying the min + repeat count
    if hasattr(us, "min_us"):
        row["min_us"] = us.min_us
        row["n_rep"] = us.n_rep
    return row


def merge_json_rows(path: str, ran_modules: list, new_rows: list,
                    profile: str) -> dict:
    """Fold this run's rows into an existing ``--json-out`` artifact.

    Rows whose ``module`` was re-run are replaced wholesale; rows of
    modules *not* in this run are preserved, so several CI jobs (each
    running ``--only`` a subset) can share one artifact file instead of
    clobbering each other's.
    """
    # top-level profile describes the MOST RECENT run; per-row "profile"
    # fields are authoritative for preserved rows
    payload = {"profile": profile, "rows": []}
    try:
        with open(path) as f:
            old = json.load(f)
        kept = [r for r in old.get("rows", [])
                if r.get("module") not in ran_modules]
        payload["rows"] = kept
    except FileNotFoundError:
        pass
    except (json.JSONDecodeError, AttributeError) as e:
        print(f"# {path} unreadable ({e}); rewriting from scratch",
              file=sys.stderr)
    payload["rows"] += new_rows
    return payload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated module substrings")
    ap.add_argument("--repeats", type=int, default=None,
                    help="override every timing loop's repetition count "
                         "(rows report median + min)")
    ap.add_argument("--json-out", default=None,
                    help="also write all rows to this JSON file (merging "
                         "into an existing artifact)")
    ap.add_argument("--roofline", action="store_true",
                    help="opt-in HLO-level roofline accounting: modules "
                         "that support it attach FLOPs/bytes + achieved-"
                         "vs-peak fractions to their rows (DESIGN.md §9)")
    ap.add_argument("--obs", action="store_true",
                    help="opt-in observability recording: runs every "
                         "module with repro.obs enabled and attaches one "
                         "registry-snapshot row per module to the JSON "
                         "artifact (DESIGN.md §19)")
    args = ap.parse_args()
    use_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    common.set_repeats(args.repeats)
    common.set_roofline(args.roofline)
    common.set_obs(args.obs)
    print("name,us_per_call,derived")
    failures = []
    all_rows = []
    ran = []
    for name, mod in MODULES:
        if args.only and not any(tok in name for tok in args.only.split(",")):
            continue
        t0 = time.time()
        print(f"# --- {name} ---", file=sys.stderr)
        csv = mod.run(quick=not args.full)
        ran.append(name)
        for row_name, us, derived in csv.rows:
            all_rows.append(_row_payload(name, row_name, us, derived,
                                         "full" if args.full else "quick"))
            if "/validate/" in row_name and "FAIL" in derived:
                failures.append((row_name, derived))
        obs_row = common.obs_snapshot_row(name,
                                          "full" if args.full else "quick")
        if obs_row is not None:
            all_rows.append(obs_row)
        print(f"# {name} done in {time.time()-t0:.0f}s", file=sys.stderr)
    if args.json_out:
        payload = merge_json_rows(args.json_out, ran, all_rows,
                                  "full" if args.full else "quick")
        with open(args.json_out, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"# wrote {args.json_out}", file=sys.stderr)
    if failures:
        print(f"# VALIDATION FAILURES: {failures}", file=sys.stderr)
        sys.exit(1)
    print("# all validations ok", file=sys.stderr)


if __name__ == "__main__":
    main()
