"""The open-loop serving cell end to end on the CPU: a tiny cell runs
correct; an answer altered where it is produced, a batch half left out, a
graph never built, or the bfloat16 control in its place, does not."""

import jax.numpy as jnp
import numpy as np
import pytest

from bench_checkout import run_cell  # also puts the harness on the path
from harness import traffic


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_every_seed_offers_the_same_gaps_in_another_order(seed):
    base = np.diff(traffic.arrival_offsets(1, 50.0, 10.0), prepend=0.0)
    got = np.diff(traffic.arrival_offsets(seed, 50.0, 10.0), prepend=0.0)
    assert got.size == 500
    np.testing.assert_allclose(np.sort(got), np.sort(base))
    assert traffic.arrival_offsets(seed, 50.0, 10.0)[-1] == pytest.approx(10.0)
    # exponential gaps: the mean is 1 / rate and the spread as wide
    assert got.mean() == pytest.approx(0.02)
    assert got.std() == pytest.approx(0.02, rel=0.1)


def test_tiny_serve_cell_is_correct(tiny_root, capsys):
    rc, res, err = run_cell(capsys, tiny_root, "tiny-serve")
    assert rc == 0, err
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"query_p90_ms", "recall_at_10", "setup_s"}
    assert res["attempted"] == 20 and res["failed"] == 0


def test_tiny_serve_cell_traced(tiny_root, capsys):
    rc, res, err = run_cell(capsys, tiny_root, "tiny-serve", "--trace", "1")
    assert rc == 0, err
    assert res["correct"], res["checks"]
    assert {"busy_s", "window_s"} <= set(res["device"])


def _answer_altered(search):
    def fake(self, queries, **kw):
        res = search(self, queries, **kw)
        return res._replace(ids=res.ids.at[0, 0].set((res.ids[0, 0] + 1) % self.index.n))
    return fake


def _half_batch(search):
    """Serve the first half of each batch and hand its answers to the rest."""
    def fake(self, queries, **kw):
        q = jnp.asarray(queries)
        if q.ndim == 1 or q.shape[0] < 2:
            return search(self, queries, **kw)
        half = q.shape[0] // 2
        res = search(self, q[:half], **kw)
        take = jnp.arange(q.shape[0]) % half
        return res._replace(ids=res.ids[take], dists=res.dists[take])
    return fake


def _state_unchanged(build):
    from repro.index import AnnIndex

    def fake(data, **kw):
        g = build(data, **kw).graph
        g = g._replace(adj0=jnp.full_like(g.adj0, -1),
                       adj_up=jnp.full_like(g.adj_up, -1))
        return AnnIndex.from_graph(g, data, backend_kind="flash_blocked")
    return fake


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch", "state_unchanged"])
def test_broken_served_path_is_not_correct(tiny_root, capsys, monkeypatch, fault):
    from repro.index import AnnIndex
    from repro.serve import SearchEngine

    if fault == "state_unchanged":
        monkeypatch.setattr(AnnIndex, "build", staticmethod(_state_unchanged(AnnIndex.build)))
    else:
        wrap = _answer_altered if fault == "answer_altered" else _half_batch
        monkeypatch.setattr(SearchEngine, "search", wrap(SearchEngine.search))
    rc, res, err = run_cell(capsys, tiny_root, "tiny-serve", seconds="1.0")
    assert rc == 0, err
    assert res["correct"] is False, (fault, res["checks"])


def test_bfloat16_control_is_not_correct(tiny_root, capsys):
    rc, res, err = run_cell(capsys, tiny_root, "tiny-serve", "--control", "1")
    assert rc == 0, err
    assert res["correct"] is False
    assert res["checks"]["dist_gap"]["value"] > res["checks"]["dist_gap"]["limit"]
