"""The benchmark's copies of the generator and the references agree with
the program's own at a tiny size, so a change to ``data/graphs.py`` or to
the program's references cannot move the yardstick unseen."""
import importlib.util
import os

import numpy as np
import pytest

from bench.ref.bfs import bfs
from bench.ref.graphs import powerlaw_graph, shard_loads
from bench.ref.pagerank import pagerank_bf16, pagerank_f64

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEEDS = [0, 7, 2**31 + 5, 3 * 2**33 + 1]
SHAPES = [(3000, 14.5, 2.1), (2048, 34.0, 1.9)]


def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("shape", SHAPES)
def test_generator_matches_program(seed, shape):
    from repro.data.graphs import make_powerlaw_graph
    ours = powerlaw_graph(*shape, seed)
    theirs = make_powerlaw_graph(*shape, seed)
    assert np.array_equal(ours[0], theirs[0])
    assert np.array_equal(ours[1], theirs[1])


def test_shard_loads_match_program_layout():
    from repro.data.graphs import shard_csr
    indptr, indices = powerlaw_graph(3001, 14.5, 2.1, 3)
    g = shard_csr(indptr, indices, 4)
    assert np.array_equal(shard_loads(indptr, 4),
                          np.asarray(g.indptr)[:, -1])


@pytest.mark.parametrize("seed", [1, 2**31 + 9])
def test_pagerank_f64_matches_chip_smoke_and_program(seed):
    from repro.algorithms.pagerank import reference_pagerank
    n = 1500
    indptr, indices = powerlaw_graph(n, 14.5, 2.1, seed)
    ours = pagerank_f64(indptr, indices, n)
    assert np.array_equal(ours, chip_smoke().ref_pagerank(indptr, indices,
                                                         n))
    fixed = np.asarray(reference_pagerank(indptr, indices, n, iters=200))
    np.testing.assert_allclose(ours, fixed, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("seed", [1, 2**31 + 9])
def test_bfs_matches_chip_smoke_and_program(seed):
    from repro.algorithms.sssp import reference_sssp
    n = 1500
    indptr, indices = powerlaw_graph(n, 14.5, 2.1, seed)
    ours = bfs(indptr, indices, n)
    assert np.array_equal(ours, chip_smoke().ref_bfs(indptr, indices, n))
    assert np.array_equal(ours, np.asarray(reference_sssp(indptr, indices,
                                                          n)))


def test_bf16_control_fails_the_pagerank_limit():
    """The control (the reference in bfloat16) reads far above the limit
    of the PageRank cells, here as on the chip."""
    import json
    n = 2048
    indptr, indices = powerlaw_graph(n, 14.5, 2.1, 4)
    gap = np.max(np.abs(pagerank_bf16(indptr, indices, n)
                        - pagerank_f64(indptr, indices, n)))
    for cell in ("dbpedia-pagerank.delta", "dbpedia-pagerank.nodelta"):
        with open(os.path.join(ROOT, "bench", "workloads",
                               cell + ".json")) as f:
            assert gap > json.load(f)["limits"]["pr_max_abs_err"]


def test_a_cell_graph_is_drawn_from_the_seed():
    from bench import run
    from bench.drivers.common import make_graph
    _, config = run.load_cell("dbpedia-pagerank.delta")
    config = dict(config, shards=4, edge_slots_per_shard=20000,
                  graph=dict(config["graph"], vertices=1000))
    a, b = make_graph(config, [5, 1]), make_graph(config, [5, 1])
    c = make_graph(config, [6, 1])
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    with pytest.raises(ValueError, match="slots"):
        make_graph(dict(config, edge_slots_per_shard=100), [5, 1])
