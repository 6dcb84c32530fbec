"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload deep96-build --seed 12345 --seconds 51 --trace 0

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout. Its configuration is the file the manifest names, its traffic mix
``bench/traffic/<traffic>.json`` (driven by ``harness.traffic``), and with
``--trace 1`` each per-layer metric is read by ``bench/metrics/<name>.py``.
Nothing here is specific to one cell.

The run sets up (inputs from the seed, programs warmed on the cell's own
shapes), measures for ``--seconds``, checks what the timed path produced
against the plain reference, and prints, in order: the window's facts
(compiles inside it, which should be none), each compared number beside
its limit on standard error, and last on standard output one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` with ``--trace 1``) and ``checks``.

It exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, or where the program under test (``src/``) is
missing. ``--control 1`` puts the reference, computed in bfloat16, in the
program's place: a run that must come out not correct.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    # appended, so that a package named like one of bench/'s directories
    # (the repository's own tests/) is found where it lives
    sys.path.append(BENCH)


class SetupError(RuntimeError):
    """The run cannot start: no chip, no program, or a cell it cannot find."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def lookup(manifest: dict, cell_name: str, root: str) -> tuple[dict, dict, dict]:
    """The cell, its configuration and its traffic mix, found by name."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if cell_name not in cells:
        raise SetupError(f"no workload {cell_name!r} in BENCHMARK.json")
    cell = cells[cell_name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic", cell["traffic"] + ".json"))
    return cell, config, traffic


def cell_metrics(entries: list, cell_name: str) -> list[dict]:
    """The metrics of a manifest list that this cell reports."""
    return [m for m in entries if cell_name in m.get("workloads", [cell_name])]


def load_reader(root: str, name: str):
    """``bench/metrics/<name>.py``'s ``read`` function."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    if spec is None:
        raise SetupError(f"no reader for per-layer metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Reading:
    """What a per-layer reader sees: the compact device trace, the
    driver's counters, the configuration and the chip's peaks."""

    def __init__(self, outcome, config: dict, traffic: dict, peaks: dict | None):
        self.record = outcome.record or {}
        self.layer = outcome.layer
        self.config = config
        self.traffic = traffic
        self.peaks = peaks


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    return args


def main(argv=None, *, root: str | None = None, src: str | None = None,
         require_chip: bool = True, compile_cache: bool = True) -> int:
    """Run a cell. ``root`` is the checkout (default: this file's), ``src``
    the program under test (default ``<root>/src``); tests run without a
    chip through ``require_chip=False``."""
    args = parse(argv)
    from harness import device

    root = root or os.path.dirname(BENCH)
    src = src or os.path.join(root, "src")
    try:
        manifest = load_json(os.path.join(root, "BENCHMARK.json"))
        cell, config, traffic = lookup(manifest, args.workload, root)
        if not os.path.isdir(os.path.join(src, "repro")):
            raise SetupError(f"no program under test at {src}")
        if src not in sys.path:
            sys.path.insert(0, src)

        import jax

        from harness import traffic as drivers

        if require_chip:
            # libtpu's own logs stay inside the checkout, not at a fixed /tmp path
            os.environ.setdefault("TPU_LOG_DIR", os.path.join(root, "bench", ".work", "tpu_logs"))
            devs = device.require_chips(int(cell["chips"]))
            peaks = device.peaks(os.path.join(BENCH, "peaks.json"), devs[0].device_kind)
        else:
            devs, peaks = jax.devices()[: int(cell["chips"])], None
        if traffic["kind"] not in drivers.DRIVERS:
            raise SetupError(f"unknown traffic kind {traffic['kind']!r}")
    except (SetupError, device.NoChip, KeyError, OSError, RuntimeError) as e:
        print(f"bench: cannot run {args.workload!r}: {e}", file=sys.stderr)
        return 2

    if compile_cache:
        device.use_compile_cache(os.path.join(root, "bench", ".jax_cache"))
    from repro import obs

    was_on = obs.enabled()
    obs.enable()  # the kernel-dispatch counter and the serving histograms
    try:
        ctx = drivers.Context(
            cell=cell, config=config, traffic=traffic, seed=args.seed,
            seconds=args.seconds, tracing=bool(args.trace),
            control=bool(args.control), devices=devs,
            clock=device.CompileClock(), t_process=T_PROCESS,
            trace_dir=os.path.join(root, "bench", ".work", "trace"),
        )
        out = drivers.DRIVERS[traffic["kind"]](ctx)
        dispatch = device.kernel_dispatch()
    finally:
        if not was_on:
            obs.disable()

    found = list(out.checks)
    if devs[0].platform == "tpu":
        off = sum(v for k, v in dispatch.items() if not k.endswith("/pallas"))
        found.append(drivers.checks.Check("non_pallas_kernels", float(off), 0.0, "<="))
    facts = {"window": out.notes, "kernel_traces_total": dispatch, "setup_s": out.setup_s}
    if args.trace:
        from harness import trace

        facts["kernels_in_trace"] = trace.kernel_names(out.record or {})
    print(json.dumps(facts), flush=True)

    if args.trace:
        reading = Reading(out, config, traffic, peaks)
        metrics = {}
        for m in cell_metrics(manifest["per_layer"], cell["name"]):
            value = load_reader(root, m["name"])(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {}
        for m in cell_metrics(manifest["end_to_end"], cell["name"]):
            if m["name"] not in out.e2e:
                print(f"bench: the driver did not measure {m['name']!r}", file=sys.stderr)
                return 3
            metrics[m["name"]] = {"value": out.e2e[m["name"]], "unit": m["unit"]}

    dev = device.describe(devs)
    dev["memory_peak_bytes"] = out.memory_peak
    result = {
        "correct": all(c.ok for c in found) and out.failed == 0,
        "attempted": out.attempted,
        "failed": out.failed,
        "metrics": metrics,
        "device": dev,
    }
    if args.trace:
        rec = out.record or {}
        w = trace.window_ns(rec)
        busy = trace.busy_ns(rec)
        dev["busy_s"] = sum(busy) / len(busy) / 1e9 if busy else 0.0
        dev["window_s"] = (w[1] - w[0]) / 1e9 if w else 0.0
        result["breakdown"] = {
            "device_ops": trace.top_ops(rec),
            "idle_gaps": trace.idle_gaps(rec),
        }
    result["checks"] = {c.name: c.as_json() for c in found}
    for c in found:
        print(c.line(), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
