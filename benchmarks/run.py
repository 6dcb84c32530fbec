"""Benchmark harness — one module per paper table/figure.

Default mode prints ``name,us_per_call,derived`` CSV rows
(benchmarks.common.emit) for every bench module.

``--json PATH`` instead runs a machine-readable suite and writes it to PATH;
``--only`` picks which one (CI uploads both artifacts):

    python benchmarks/run.py --json BENCH_indexing.json   # width sweep +
                                                          # dynamic update
    python benchmarks/run.py --json BENCH_serving.json --only serving
    python benchmarks/run.py --json BENCH_kernels.json --only kernels
    python benchmarks/run.py --json BENCH_search.json --only search
    python benchmarks/run.py --json BENCH_scalability.json --only scalability

``--repeats N`` (default 3) runs every timed section N times; medians are
reported and the raw samples recorded in the JSON (2-core container noise).

  bench_indexing     Figures 6, 7 + Table 4   (build time / size / coding time)
  bench_search       Figures 8, 9             (QPS-Recall, QPS-ADR)
  bench_scalability  Figures 10, 11           (volume + segment scaling; the
                                              JSON suite runs the streaming
                                              million-vector sharded tier)
  bench_simd         Figure 12 + Table 3      (batch-width sweep, SIMD on/off)
  bench_generality   Figures 13, 14           (Vamana / NSG with Flash)
  bench_memory       Table 2 + Figures 1, 15  (NMA/bytes model, time profile)
  bench_params       Figures 3, 4, 16         (parameter sensitivity)
  bench_retrieval    beyond-paper             (retrieval_cand serving cell)
  bench_serving      beyond-paper             (repro.serve: snapshot +
                                              shape-bucketed QPS + batching
                                              speedup, DESIGN.md §9)
  bench_kernels      beyond-paper             (scan vs fused-expand kernel
                                              microbench, DESIGN.md §10)

Roofline terms per (arch × shape) come from the dry-run, not this harness:
``python -m repro.launch.dryrun`` (see EXPERIMENTS.md §Roofline).
"""

import argparse
import datetime
import json
import pathlib
import subprocess
import sys
import traceback

# `python benchmarks/run.py` puts benchmarks/ (not the repo root) first on
# sys.path, which breaks the `benchmarks.*` package imports below; anchor
# the root explicitly so the documented CI invocation works from anywhere.
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def _json_indexing_widths(repeats: int) -> tuple[dict, list[str]]:
    from benchmarks import bench_indexing

    payload = bench_indexing.width_sweep(repeats=repeats)
    payload["update"] = bench_indexing.update_bench(repeats=repeats)
    payload["bulk_vs_incremental"] = bench_indexing.bulk_vs_incremental(
        repeats=repeats
    )
    warnings = []
    for w, row in payload["bulk_vs_incremental"]["widths"].items():
        if row["throughput_ratio"] < 2.0:
            warnings.append(
                f"bulk build throughput only {row['throughput_ratio']:.2f}x "
                f"incremental at width={w} (acceptance bar: >= 2x)"
            )
        if abs(row["recall_delta"]) > 0.005:
            warnings.append(
                f"bulk recall@10 delta {row['recall_delta']:+.4f} at "
                f"width={w} outside the +/-0.005 acceptance band"
            )
    upd = payload["update"]["add"]
    if upd["n_dists_vs_rebuild"] >= 0.5:
        warnings.append(
            f"add() cost {upd['n_dists_vs_rebuild']:.2f} of a full "
            "rebuild's distance evaluations (acceptance bar: < 0.5)"
        )
    widths = payload["widths"]
    base = widths.get("1")
    if base:
        worse = [
            w for w, row in widths.items()
            if w != "1" and row["us_per_dist"] >= base["us_per_dist"]
        ]
        if worse:
            warnings.append(
                f"width(s) {worse} did not beat width=1 on us_per_dist"
            )
    return payload, warnings


def _json_serving(repeats: int) -> tuple[dict, list[str]]:
    from benchmarks import bench_serving

    payload = bench_serving.serving_bench(repeats=repeats)
    warnings = []
    if payload["engine"]["recompiles_after_warmup"]:
        warnings.append(
            "serving engine recompiled after warmup "
            f"({payload['engine']['recompiles_after_warmup']} traces)"
        )
    speedup = payload["batching"]["speedup"]
    if speedup < bench_serving.SPEEDUP_BAR:
        warnings.append(
            f"batched serving speedup {speedup:.2f}x below the "
            f"{bench_serving.SPEEDUP_BAR:.0f}x acceptance bar"
        )
    mixed = payload["mixed"]
    if mixed["speedup_vs_sequential"] < bench_serving.MIXED_SPEEDUP_BAR:
        warnings.append(
            f"mixed-workload QPS {mixed['speedup_vs_sequential']:.2f}x "
            f"sequential, below the {bench_serving.MIXED_SPEEDUP_BAR:.0f}x "
            "acceptance bar"
        )
    if mixed["p99_ratio"] > bench_serving.MIXED_P99_RATIO_BAR:
        warnings.append(
            f"mixed-workload p99 {mixed['mixed']['p99_ms']:.2f}ms is "
            f"{mixed['p99_ratio']:.2f}x the read-only p99 (bar: <= "
            f"{bench_serving.MIXED_P99_RATIO_BAR:.0f}x)"
        )
    if mixed["mixed"]["shed_rate"] > bench_serving.SHED_RATE_BAR:
        warnings.append(
            f"mixed-workload shed rate {mixed['mixed']['shed_rate']:.4f} "
            f"exceeds the {bench_serving.SHED_RATE_BAR:.2f} bar"
        )
    if mixed["cold_dispatches"]:
        warnings.append(
            f"mixed workload hit {mixed['cold_dispatches']} cold "
            "dispatches — a generation flip published without pre-warming"
        )
    return payload, warnings


def _json_kernels(repeats: int) -> tuple[dict, list[str]]:
    from benchmarks import bench_kernels

    payload = bench_kernels.kernels_bench(repeats=repeats)
    warnings = []
    slow = [
        w for w, row in payload["expand_width_sweep"]["widths"].items()
        if row["speedup"] < 1.0
    ]
    if slow:
        warnings.append(
            f"fused expand did not beat the unfused gather+scan at width(s) "
            f"{slow} (microbench on a 2-core box — check the *_us_samples "
            "arrays in the JSON before reading this as a regression)"
        )
    return payload, warnings


def _json_search(repeats: int) -> tuple[dict, list[str]]:
    from benchmarks import bench_search

    payload = bench_search.search_bench(repeats=repeats)
    warnings = []
    acc = payload["acceptance"]
    if acc["recall_gap_at_mult4"] > acc["recall_gap_bar"]:
        warnings.append(
            f"rerank_mult=4 recall@10 gap vs fp32 "
            f"{acc['recall_gap_at_mult4']:.4f} exceeds the "
            f"{acc['recall_gap_bar']:.3f} acceptance bar"
        )
    if acc["fp32_work_vs_fp32_scan_at_mult4"] > acc["fp32_fraction_bar"]:
        warnings.append(
            "rerank_mult=4 full-precision work "
            f"{acc['fp32_work_vs_fp32_scan_at_mult4']:.2f} of fp32's scan "
            f"evaluations (bar: <= {acc['fp32_fraction_bar']:.2f})"
        )
    if payload["serving"]["recompiles_after_warmup"]:
        warnings.append(
            "reranked serving spec recompiled after warmup "
            f"({payload['serving']['recompiles_after_warmup']} traces)"
        )
    return payload, warnings


def _json_scalability(repeats: int) -> tuple[dict, list[str]]:
    from benchmarks import bench_scalability

    payload = bench_scalability.scalability_bench(repeats=repeats)
    warnings = []
    acc = payload["acceptance"]
    workers = payload["build"]["speedup_modeled"]["workers"]
    # the 2.5x bar is stated for the full tier's 4 workers; a reduced
    # CI tier with w workers can never exceed w x, so scale the bar down
    speedup_bar = min(bench_scalability.SPEEDUP_BAR, 0.85 * workers)
    if acc["speedup_modeled_vs_1w"] < speedup_bar:
        warnings.append(
            f"modeled {workers}-worker "
            f"build speedup {acc['speedup_modeled_vs_1w']:.2f}x below the "
            f"{speedup_bar:.1f}x acceptance bar"
        )
    if acc["us_per_dist_ratio_vs_single_segment"] > (
        bench_scalability.US_PER_DIST_RATIO_BAR
    ):
        warnings.append(
            "sharded us/dist is "
            f"{acc['us_per_dist_ratio_vs_single_segment']:.2f}x the "
            "single-segment baseline (bar: <= "
            f"{bench_scalability.US_PER_DIST_RATIO_BAR:.2f}x)"
        )
    if acc["recall_delta_vs_sequential"] > bench_scalability.RECALL_DELTA_BAR:
        warnings.append(
            f"sharded recall@10 differs from the sequential segmented build "
            f"by {acc['recall_delta_vs_sequential']:.4f} (bar: <= "
            f"{bench_scalability.RECALL_DELTA_BAR:.2f})"
        )
    if not acc["pool_bit_exact"]:
        warnings.append(
            "pool-built index is not bit-exact with the sequential "
            "segmented build over the same assignment"
        )
    return payload, warnings


#: --only suite name -> builder returning (payload, warning strings).
JSON_SUITES = {
    "indexing_widths": _json_indexing_widths,
    "serving": _json_serving,
    "kernels": _json_kernels,
    "search": _json_search,
    "scalability": _json_scalability,
}


def _run_meta(only: str, repeats: int) -> dict:
    """Provenance stamp for every BENCH_*.json: without the producing
    commit and toolchain version, cross-PR perf trajectories can't be
    diffed trustworthily."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=pathlib.Path(__file__).resolve().parents[1],
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
    except Exception:  # noqa: BLE001 — no git / not a checkout
        sha = None
    import jax

    return {
        "git_sha": sha,
        "timestamp_utc": datetime.datetime.now(
            datetime.timezone.utc
        ).isoformat(timespec="seconds"),
        "jax_version": jax.__version__,
        "suite": only,
        "repeats": repeats,
    }


def run_json(path: str, only: str, repeats: int) -> None:
    """Machine-readable perf snapshot (build/serve trajectory across PRs).

    Every timed section runs ``repeats`` times (median reported, raw
    samples recorded in the JSON) — single-shot timings on this 2-core
    container flap with scheduler noise.
    """
    suite = JSON_SUITES.get(only)
    if suite is None:
        raise SystemExit(
            f"unknown --only {only!r} (have: {', '.join(JSON_SUITES)})"
        )
    print("name,us_per_call,derived")
    payload, warnings = suite(repeats)
    payload["meta"] = _run_meta(only, repeats)
    from repro import obs

    payload["obs"] = obs.snapshot()
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"wrote {path}", file=sys.stderr)
    for msg in warnings:
        print(f"WARNING: {msg}", file=sys.stderr)


def run_csv() -> None:
    from benchmarks import (
        bench_generality,
        bench_indexing,
        bench_kernels,
        bench_memory,
        bench_params,
        bench_retrieval,
        bench_scalability,
        bench_search,
        bench_serving,
        bench_simd,
    )

    print("name,us_per_call,derived")
    failures = []
    for mod in (
        bench_indexing, bench_search, bench_scalability, bench_simd,
        bench_generality, bench_memory, bench_params, bench_retrieval,
        bench_serving, bench_kernels,
    ):
        try:
            mod.run()
        except Exception as e:  # noqa: BLE001
            failures.append((mod.__name__, e))
            traceback.print_exc()
    if failures:
        print(f"FAILED benches: {[m for m, _ in failures]}", file=sys.stderr)
        raise SystemExit(1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--json", metavar="PATH", default=None,
        help="write the machine-readable width-sweep snapshot to PATH "
        "instead of running the CSV bench suite",
    )
    ap.add_argument(
        "--only", default="indexing_widths",
        help="which JSON suite to run (with --json): "
        f"{', '.join(JSON_SUITES)}; default indexing_widths",
    )
    ap.add_argument(
        "--repeats", type=int, default=3, metavar="N",
        help="run each timed section N times; the median is reported and "
        "all samples land in the JSON (default 3 — the 2-core container "
        "needs it)",
    )
    args = ap.parse_args()
    from repro.utils import use_compile_cache

    use_compile_cache(str(pathlib.Path(__file__).resolve().parents[1]))
    if args.json:
        run_json(args.json, args.only, args.repeats)
    else:
        run_csv()


if __name__ == '__main__':
    main()
