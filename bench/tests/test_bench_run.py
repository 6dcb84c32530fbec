"""``bench/run.py`` end to end on the CPU, with the build cell: no chip
means no result; a tiny cell found by name runs correct; the timed path
broken underneath, or the bfloat16 control in its place, does not."""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from bench_checkout import BENCH, ROOT, TINY_CONFIG, last_json, run_cell, write_checkout

import run  # noqa: E402 — bench/run.py, on the path through bench_checkout


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "deep96-build", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert last_json(p.stdout) is None


def test_no_program_no_result(tmp_path, capsys):
    root = str(tmp_path)
    write_checkout(root)
    rc = run.main(["--workload", "tiny-build", "--seed", "1", "--seconds", "1"],
                  root=root, require_chip=False, compile_cache=False)
    out = capsys.readouterr()
    assert rc != 0 and last_json(out.out) is None
    assert "no program under test" in out.err


def test_cell_config_traffic_and_metric_found_by_name(tmp_path, capsys):
    """A configuration, a traffic mix and a per-layer reader dropped into a
    checkout are run by name, with no edit to the harness."""
    root = str(tmp_path)
    reader = ("def read(r):\n"
              "    return float(r.config['n'] + r.traffic['check_queries'])\n")
    silent = "def read(r):\n    return None\n"
    write_checkout(root, readers={"dropped_in": reader, "finds_nothing": silent},
                   per_layer=[
                       {"name": "dropped_in", "unit": "n", "better": "higher",
                        "source": "program_counter", "layer": "test",
                        "moves": "build_vps"},
                       {"name": "finds_nothing", "unit": "n", "better": "higher",
                        "source": "program_counter", "layer": "test",
                        "moves": "build_vps"},
                   ])
    rc, res, err = run_cell(capsys, root, "tiny-build", "--trace", "1")
    assert rc == 0, err
    assert res["metrics"] == {"dropped_in": {"value": 1564.0, "unit": "n"}}
    assert {"busy_s", "window_s", "platform", "count"} <= set(res["device"])
    assert list(res)[-1] == "checks"


def test_tiny_cell_is_correct(tiny_root, capsys):
    rc, res, err = run_cell(capsys, tiny_root, "tiny-build")
    assert rc == 0, err
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"build_vps", "recall_at_10", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert "check recall_at_10=" in err


def _state_unchanged(build):
    from repro.index import AnnIndex

    def fake(data, **kw):
        idx = build(data, **kw)
        g = idx.graph
        g = g._replace(adj0=jnp.full_like(g.adj0, -1),
                       adj0_d=jnp.full_like(g.adj0_d, jnp.inf),
                       adj_up=jnp.full_like(g.adj_up, -1))
        return AnnIndex.from_graph(g, data, backend_kind="flash_blocked")
    return fake


def _half_batch(build):
    def fake(data, **kw):
        return build(np.asarray(data)[: len(data) // 2], **kw)
    return fake


def _edge_altered(build):
    from repro.index import AnnIndex

    def fake(data, **kw):
        idx = build(data, **kw)
        g = idx.graph
        g = g._replace(adj0=g.adj0.at[5, 0].set(5))
        return AnnIndex.from_graph(g, data, backend_kind="flash_blocked")
    return fake


def _answer_altered(search):
    def fake(self, queries, *a, **kw):
        res = search(self, queries, *a, **kw)
        ids = res.ids.at[0, 0].set((res.ids[0, 0] + 1) % self.n)
        return res._replace(ids=ids)
    return fake


@pytest.mark.parametrize("fault, attr, wrap", [
    ("state_unchanged", "build", _state_unchanged),
    ("half_batch", "build", _half_batch),
    ("edge_altered", "build", _edge_altered),
    ("answer_altered", "search", _answer_altered),
])
def test_broken_timed_path_is_not_correct(tiny_root, capsys, monkeypatch,
                                          fault, attr, wrap):
    from repro.index import AnnIndex

    orig = getattr(AnnIndex, attr)
    if attr == "build":
        monkeypatch.setattr(AnnIndex, "build", staticmethod(wrap(orig)))
    else:
        monkeypatch.setattr(AnnIndex, "search", wrap(orig))
    rc, res, err = run_cell(capsys, tiny_root, "tiny-build")
    assert rc == 0, err
    assert res["correct"] is False, (fault, res["checks"])


def test_bfloat16_control_is_not_correct(tiny_root, capsys):
    rc, res, err = run_cell(capsys, tiny_root, "tiny-build", "--control", "1")
    assert rc == 0, err
    assert res["correct"] is False
    assert res["checks"]["dist_gap"]["value"] > res["checks"]["dist_gap"]["limit"]


#: an inner-product deployment with out-of-distribution queries, in
#: miniature: rows not unit-normalised, queries of their own spectrum and
#: shifted off the rows, as text queries over image embeddings are
TINY_IP_CONFIG = dict(
    TINY_CONFIG, metric="inner product", distance="ip",
    queries={"spectrum": "linear", "scale_hi": 0.2, "scale_lo": 1.0, "offset": 2.0},
)


@pytest.fixture(scope="module")
def ip_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("ip_checkout"))
    write_checkout(root, config=TINY_IP_CONFIG)
    return root


@pytest.mark.parametrize("cell", ["tiny-build", "tiny-serve"])
def test_inner_product_cell_holds_the_l2_program_to_its_metric(ip_root, capsys, cell):
    """A configuration that states ``"distance": "ip"`` and a ``queries``
    block, dropped into a checkout as files alone, runs by name, built or
    served; the program, which ranks by squared L2 only, comes out not
    correct."""
    rc, res, err = run_cell(capsys, ip_root, cell)
    assert rc == 0, err
    assert res["correct"] is False
    found = res["checks"]
    assert found["malformed"]["value"] == 0 and res["failed"] == 0
    assert found["dist_gap"]["value"] > found["dist_gap"]["limit"], found
    # the squared-L2 top-10 is another answer on rows of unequal norms
    assert found["recall_at_10"]["value"] < found["recall_at_10"]["limit"], found


def _ranked_by_inner_product(search):
    def fake(self, queries, *a, **kw):
        res = search(self, queries, *a, **kw)
        k = res.ids.shape[1]
        d = -np.asarray(queries, np.float32) @ np.asarray(self._data, np.float32).T
        ids = np.argsort(d, axis=1, kind="stable")[:, :k]
        return res._replace(ids=jnp.asarray(ids, jnp.int32),
                            dists=jnp.asarray(np.take_along_axis(d, ids, axis=1)))
    return fake


def test_inner_product_cell_passes_answers_ranked_by_inner_product(
        ip_root, capsys, monkeypatch):
    """The same cell with the search's answers replaced by a float32 brute
    force by inner product: correct, so the cell can be passed."""
    from repro.index import AnnIndex

    monkeypatch.setattr(AnnIndex, "search", _ranked_by_inner_product(AnnIndex.search))
    rc, res, err = run_cell(capsys, ip_root, "tiny-build")
    assert rc == 0, err
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["recall_at_10"]["value"] == 1.0


@pytest.mark.parametrize("cell", ["tiny-build", "tiny-serve"])
def test_inner_product_control_is_not_correct(ip_root, capsys, cell):
    rc, res, err = run_cell(capsys, ip_root, cell, "--control", "1")
    assert rc == 0, err
    assert res["correct"] is False
    assert res["checks"]["dist_gap"]["value"] > res["checks"]["dist_gap"]["limit"]


def test_manifest_names_files_that_exist():
    """Every cell's configuration and traffic mix, and every per-layer
    metric's reader, is where the harness looks for it by name."""
    manifest = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for cell in manifest["workloads"]:
        _, config, traffic = run.lookup(manifest, cell["name"], ROOT)
        assert traffic["kind"] in ("build_stream", "open_loop")
        assert config["name"] == cell["config"]
    for m in manifest["per_layer"]:
        assert callable(run.load_reader(ROOT, m["name"]))
