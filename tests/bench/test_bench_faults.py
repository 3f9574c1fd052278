"""With the timed path broken underneath, a run comes out not correct:
once for each fault a cell can have.  Each test drives the whole of a
tiny run on the CPU (everything but the harness's look for a chip)."""
import jax
import jax.numpy as jnp
import pytest

from tiny import execute_tiny

FIXPOINT_CELLS = ["dbpedia-pagerank.delta", "dbpedia-pagerank.nodelta"]


@pytest.fixture(autouse=True)
def fresh_traces():
    """Planted faults must be traced anew, and must not outlive the test
    in a cached program."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def keep_state(monkeypatch):
    """A fixpoint that returns its state unchanged."""
    from repro.core import engine
    from repro.core.fixpoint import FixpointResult, empty_stats

    def run(self, algo, state0, live0, immutable, max_iters, mode="delta",
            explicit_cond=None):
        return FixpointResult(state=state0, stats=empty_stats(max_iters))
    monkeypatch.setattr(engine.ShardedExecutor, "run", run)


def drop_half_edges(monkeypatch):
    """Half of the work left out: every other edge slot emits nothing."""
    from repro.algorithms import emission
    sparse, dense = emission.emit_over_edges, emission.dense_push

    def emit(graph, active, payload, src_cap, edge_cap):
        db = sparse(graph, active, payload, src_cap, edge_cap)
        odd = jnp.arange(db.keys.shape[0]) % 2 == 1
        return type(db)(keys=jnp.where(odd, -1, db.keys), payload=db.payload,
                        ann=db.ann, count=db.count,
                        overflowed=db.overflowed)

    def push(graph, payload):
        dst, pay = dense(graph, payload)
        odd = jnp.arange(dst.shape[0]) % 2 == 1
        return jnp.where(odd, -1, dst), pay
    monkeypatch.setattr(emission, "emit_over_edges", emit)
    monkeypatch.setattr(emission, "dense_push", push)


def no_exchange(monkeypatch):
    """The exchange between shards left out: each shard keeps only what
    it sent to itself."""
    from repro.core import delta as deltamod
    from repro.core import engine
    sparse = engine.ShardedExecutor.rehash_sparse_simulated
    dense = engine.ShardedExecutor.rehash_dense_simulated

    def rehash_sparse(self, stacked, seg_capacity=None, combiner=None,
                      route=0):
        incoming, emitted = sparse(self, stacked, seg_capacity, combiner,
                                   route)
        S = self.snapshot.num_shards
        cap = incoming.keys.shape[1] // S
        src = jnp.arange(S * cap)[None, :] // cap
        own = src == jnp.arange(S)[:, None]
        keys = jnp.where(own, incoming.keys, deltamod.PAD_KEY)
        fixed = type(incoming)(keys=keys, payload=incoming.payload,
                               ann=incoming.ann, count=incoming.count,
                               overflowed=incoming.overflowed)
        return jax.vmap(deltamod.recount)(fixed), emitted

    def rehash_dense(self, contrib, combiner):
        S, block = self.snapshot.num_shards, self.snapshot.block_size
        seg = contrib.reshape(S, S, block, contrib.shape[-1])
        return seg[jnp.arange(S), jnp.arange(S)]
    monkeypatch.setattr(engine.ShardedExecutor, "rehash_sparse_simulated",
                        rehash_sparse)
    monkeypatch.setattr(engine.ShardedExecutor, "rehash_dense_simulated",
                        rehash_dense)


def alter_rank(monkeypatch):
    """One answer altered where it is produced: the first vertex's rank
    comes out one higher."""
    from repro.algorithms import pagerank
    run = pagerank.run

    def altered(*args, **kw):
        pr, res = run(*args, **kw)
        return pr.at[0].add(1.0), res
    monkeypatch.setattr(pagerank, "run", altered)


FIXPOINT_FAULTS = [keep_state, drop_half_edges, no_exchange, alter_rank]


@pytest.mark.parametrize("cell", FIXPOINT_CELLS)
@pytest.mark.parametrize("fault", FIXPOINT_FAULTS, ids=lambda f: f.__name__)
def test_fixpoint_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    result = execute_tiny(cell)
    assert not result["correct"]
    assert result["failed"] == result["attempted"] > 0
    check = result["checks"]["pr_max_abs_err"]
    assert check["value"] > check["limit"]


SHARD_MAP_NO_EXCHANGE = '''
import json, sys, jax, jax.numpy as jnp
sys.path[:0] = [ROOT, HERE]
from repro.core import delta as deltamod
from repro.core import engine
from tiny import execute_tiny

def rehash_sparse(self, db, seg_capacity=None, combiner=None, route=0):
    cap = self.seg_capacity if seg_capacity is None else seg_capacity
    S = self.snapshot.num_shards
    routed = self._route_one(db, cap, combiner, route)
    me = jax.lax.axis_index(self.axis_name)
    own = (jnp.arange(S * cap) // cap) == me
    out = type(routed)(keys=jnp.where(own, routed.keys, deltamod.PAD_KEY),
                       payload=routed.payload, ann=routed.ann,
                       count=routed.count, overflowed=routed.overflowed)
    return deltamod.recount(out), jax.lax.psum(routed.count, self.axis_name)

def rehash_dense(self, contrib, combiner):
    S, block = self.snapshot.num_shards, self.snapshot.block_size
    me = jax.lax.axis_index(self.axis_name)
    return jax.lax.dynamic_slice_in_dim(contrib, me * block, block, 0)

engine.ShardedExecutor.rehash_sparse_shard_map = rehash_sparse
engine.ShardedExecutor.rehash_dense_shard_map = rehash_dense
for cell in CELLS:
    print(json.dumps(execute_tiny(cell, devices=jax.devices()[:4])))
'''


def test_shard_map_without_the_exchange_between_chips_is_not_correct():
    """The four-chip cell with its all_to_all left out (each chip keeps
    what it routed to itself), on four virtual CPU devices."""
    import json
    import os
    import subprocess
    import sys

    from bench import run
    from tiny import CELLS
    cells = [c for c in CELLS if run.load_cell(c)[1]["backend"] ==
             "shard_map"]
    here = os.path.dirname(os.path.abspath(__file__))
    code = (f"ROOT, HERE, CELLS = {run.ROOT!r}, {here!r}, {cells!r}\n"
            + SHARD_MAP_NO_EXCHANGE)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(run.ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    assert len(lines) == len(cells) >= 1
    for r in lines:
        assert not r["correct"]
        check = r["checks"]["pr_max_abs_err"]
        assert check["value"] > check["limit"]
