"""A configuration that states no ``distance`` and no ``queries`` block is
run as before they existed: its inputs, its reference and every check's
value are the same bytes as with ``"distance": "l2"`` written out, and the
same bytes as the harness made before either key existed."""

import glob
import hashlib
import json
import os

import numpy as np
import pytest

from bench_checkout import BENCH
from harness import checks, data, reference

CONFIGS = sorted(glob.glob(os.path.join(BENCH, "configs", "*.json")))
NAMES = ("base", "queries", "truth", "truth_d", "ids", "dists", "exact", "checks")
#: the first 16 hex digits of each array's sha256, as the harness made them
#: before configurations could state ``distance`` or ``queries`` (XLA's CPU
#: backend, jax 0.9.0; n = 1200, 24 queries, seed 2**31 + 7)
BEFORE = {
    "deep-96.json": {
        "base": "59a2606416fd4691", "queries": "f26edfa1516a374c",
        "truth": "abce5d3609ce551e", "truth_d": "a2880a599511030e",
        "ids": "1541df772af8150d", "dists": "c384480db3f945f9",
        "exact": "1f260a7e56d28795", "checks": "d74bd271f9961e96",
    },
    "laion-768.json": {
        "base": "f87b139623a3efb9", "queries": "500e0100051e5eee",
        "truth": "b9d2ceaa83ccb5e6", "truth_d": "1b027cc8edfe07fd",
        "ids": "20e920820f4b8385", "dists": "0c64b27b53898d35",
        "exact": "f5d03a187af44ec2", "checks": "2f02e86c6d55409b",
    },
}


def _plain(path):
    """The configuration at ``path`` without the two keys, at a tiny n."""
    with open(path) as f:
        cfg = json.load(f)
    return dict({k: v for k, v in cfg.items() if k not in ("distance", "queries")}, n=1200)


def _run(cfg, seed):
    """Inputs, reference and checks of a tiny run, as `build_stream`
    makes them, with the bfloat16 control's answers."""
    distance = reference.distance_of(cfg)
    base, queries = data.inputs(cfg, seed, 24)
    truth, truth_d = reference.topk(base, queries, 10, distance=distance)
    ids, dists = reference.topk(base, queries, 10, distance=distance,
                                precision="bfloat16")
    exact = reference.distances(base, queries, ids, distance=distance)
    found = checks.answer_checks(ids, dists, base, queries, truth, cfg["limits"],
                                 distance=distance)
    return [base, queries, truth, truth_d, ids, dists, exact,
            np.array([c.value for c in found])]


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_default_distance_is_l2_bit_for_bit(path):
    plain = _plain(path)
    seed = 2**31 + 7
    got = _run(plain, seed)
    want = _run(dict(plain, distance="l2"), seed)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    # the control's gap is wide, so the values compared are not all 0
    assert got[-1][1] > 1e-3


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_default_path_matches_the_harness_before_the_keys(path):
    """Every array and every check's value hashes as it did before a
    configuration could state ``distance`` or ``queries``, so the default
    path's draw, reference, control and checks are unchanged."""
    got = _run(_plain(path), 2**31 + 7)
    digests = {name: hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]
               for name, a in zip(NAMES, got)}
    assert digests == BEFORE[os.path.basename(path)]
