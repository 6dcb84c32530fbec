"""A checkout in a temporary directory holding one tiny cell, for the
benchmark's own tests."""

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
if BENCH not in sys.path:
    # appended, so that a package named like one of bench/'s directories
    # (the repository's own tests/) is found where it lives
    sys.path.append(BENCH)

#: a cell small enough for the CPU: the deep-96 index path at d=32
TINY_CONFIG = {
    "name": "tiny",
    "source": "test",
    "n": 1500,
    "dim": 32,
    "data_seed": 0,
    "generator": {"kind": "gmm", "n_clusters": 8, "sep": 1.0,
                  "spectrum": "linear", "scale_hi": 1.0, "scale_lo": 0.2},
    "index": {"algo": "hnsw", "backend": "flash_blocked", "strategy": "bulk",
              "build_seed": 0, "params": {},
              "coder": {"d_f": 32, "m_f": 16, "kmeans_iters": 4}},
    "search": {"k": 10, "ef": 64, "rerank": "exact", "rerank_mult": None},
    "limits": {"recall_at_10": 0.9, "dist_gap": 1e-4},
}


TINY_STREAM = {"kind": "build_stream", "check_queries": 64}
TINY_OPEN_LOOP = {"kind": "open_loop", "rate": 40.0, "max_wait_ms": 2.0,
                  "drain_seconds": 60, "trace_seconds": 0.5}


def write_checkout(root, *, config=None, traffic=None, readers=None,
                   per_layer=None):
    """A checkout with BENCHMARK.json and bench/{configs,traffic,metrics}
    holding two cells, ``tiny-build`` and ``tiny-serve``."""
    config = config or TINY_CONFIG
    traffic = traffic or TINY_STREAM
    for sub in ("configs", "traffic", "metrics"):
        os.makedirs(os.path.join(root, "bench", sub), exist_ok=True)
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "bench", "traffic", "tiny_stream.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "bench", "traffic", "tiny_open_loop.json"), "w") as f:
        json.dump(TINY_OPEN_LOOP, f)
    for name, body in (readers or {}).items():
        with open(os.path.join(root, "bench", "metrics", name + ".py"), "w") as f:
            f.write(body)
    manifest = {
        "command": ["python3", "bench/run.py"],
        "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "tiny-build", "config": "tiny",
                       "traffic": "tiny_stream", "chips": 1, "why": "test"},
                      {"name": "tiny-serve", "config": "tiny",
                       "traffic": "tiny_open_loop", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "build_vps", "unit": "vectors/s", "better": "higher",
             "bound": 0.05, "source": "host_clock", "workloads": ["tiny-build"]},
            {"name": "query_p90_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock", "workloads": ["tiny-serve"]},
            {"name": "recall_at_10", "unit": "frac", "better": "higher",
             "bound": 0.01, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
             "source": "host_clock"},
        ],
        "per_layer": per_layer or [],
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)




def last_json(text: str):
    lines = [ln for ln in text.strip().splitlines() if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def run_cell(capsys, root, cell, *extra, seconds="0.5"):
    """bench/run.py in this process, without a chip; (rc, result, stderr)."""
    import run

    rc = run.main(["--workload", cell, "--seed", "2147483659",
                   "--seconds", seconds, *extra],
                  root=root, src=SRC, require_chip=False, compile_cache=False)
    out = capsys.readouterr()
    return rc, last_json(out.out), out.err
