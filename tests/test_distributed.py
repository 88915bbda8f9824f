"""Node-sharded shard_map protocol step on the local 1-device mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import walkers as wlk
from repro.core.distributed import make_sharded_step
from repro.core.protocol import ProtocolConfig
from repro.graphs import GraphState, availability, random_regular_graph
from jax import make_mesh
from jax.sharding import AxisType
from repro.utils.prng import fold_in_time


@pytest.fixture(scope="module")
def setup():
    g = random_regular_graph(64, 8, seed=1)
    pcfg = ProtocolConfig(
        algorithm="decafork+", z0=6, max_walks=24, eps=1.8, eps2=6.5,
        protocol_start=200, rt_bins=256,
    )
    mesh = make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    step = jax.jit(make_sharded_step(mesh, ("data",), g.n, pcfg))
    return g, pcfg, mesh, step


def _init(g, pcfg, key):
    W = pcfg.max_walks
    pos = jax.random.randint(key, (W,), 0, g.n, dtype=jnp.int32)
    active = jnp.arange(W) < pcfg.z0
    track = jnp.arange(W, dtype=jnp.int32)
    last_seen = jnp.full((g.n, W), -1, jnp.int32)
    hist = jnp.zeros((g.n, pcfg.rt_bins), jnp.float32)
    total = jnp.zeros((g.n,), jnp.float32)
    return pos, active, track, last_seen, hist, total


def _full_masks(g):
    return jnp.ones((g.n,), bool), jnp.ones((g.n, g.max_degree), bool)


@pytest.mark.slow
def test_distributed_step_runs_and_self_regulates(setup):
    g, pcfg, mesh, step = setup
    key = jax.random.key(0)
    pos, active, track, last_seen, hist, total = _init(g, pcfg, key)
    nbrs, degs = jnp.asarray(g.neighbors), jnp.asarray(g.degrees)
    node_up, edge_up = _full_masks(g)
    t = jnp.int32(0)
    zs = []
    with mesh:
        for _ in range(600):
            t, pos, active, track, last_seen, hist, total, key, z = step(
                t, pos, active, track, last_seen, hist, total, key, nbrs, degs,
                node_up, edge_up,
            )
            zs.append(int(z))
    zs = np.asarray(zs)
    assert zs.min() >= 1  # resilience objective
    assert zs.max() <= pcfg.max_walks
    assert float(total.sum()) > 0  # return-time samples accumulated
    # movement stays on the graph
    assert (np.asarray(pos) >= 0).all() and (np.asarray(pos) < g.n).all()


def test_distributed_movement_follows_edges(setup):
    g, pcfg, mesh, step = setup
    key = jax.random.key(1)
    pos, active, track, last_seen, hist, total = _init(g, pcfg, key)
    nbrs, degs = jnp.asarray(g.neighbors), jnp.asarray(g.degrees)
    node_up, edge_up = _full_masks(g)
    adj = g.adjacency()
    t = jnp.int32(0)
    with mesh:
        for _ in range(25):
            old_pos = np.asarray(pos)
            t, pos, active, track, last_seen, hist, total, key, z = step(
                t, pos, active, track, last_seen, hist, total, key, nbrs, degs,
                node_up, edge_up,
            )
            new_pos = np.asarray(pos)
            act = np.asarray(active)
            for w in range(pcfg.max_walks):
                if act[w] and old_pos[w] != new_pos[w]:
                    assert adj[old_pos[w], new_pos[w]], (old_pos[w], new_pos[w])


def test_distributed_masked_movement_parity_with_single_device(setup):
    """GraphState masks through the shard_map'd step: resident-walk kills
    and masked movement match the single-device path (kill_resident_walks
    + walkers.move_walks over the same availability) bit-for-bit."""
    g, pcfg, mesh, step = setup
    nbrs, degs = jnp.asarray(g.neighbors), jnp.asarray(g.degrees)
    rng = np.random.default_rng(7)
    node_up = jnp.asarray(rng.random(g.n) > 0.15)
    edge_np = rng.random((g.n, g.max_degree)) > 0.2
    # keep the mask symmetric like step_topology does (not required for
    # parity, but it is the state space the simulator actually produces)
    for i in range(g.n):
        for k in range(int(g.degrees[i])):
            j = int(g.neighbors[i, k])
            if j > i:
                kk = int(np.nonzero(np.asarray(g.neighbors[j]) == i)[0][0])
                edge_np[j, kk] = edge_np[i, k]
    edge_up = jnp.asarray(edge_np)
    gs = GraphState(node_up=node_up, edge_up=edge_up)
    avail = availability(gs, nbrs, degs)

    key = jax.random.key(3)
    pos, active, track, last_seen, hist, total = _init(g, pcfg, key)
    t = jnp.int32(0)
    with mesh:
        for _ in range(8):
            # single-device reference for this round, same key stream
            ref_active = active & node_up[pos]
            ws = wlk.WalkState(pos=pos, active=ref_active, track=track)
            ref = wlk.move_walks(
                ws, nbrs, degs, fold_in_time(key, t, 0), avail
            )
            t, pos, active, track, last_seen, hist, total, key, z = step(
                t, pos, active, track, last_seen, hist, total, key, nbrs, degs,
                node_up, edge_up,
            )
            # protocol_start=200 >> t: no forks/terminations interfere
            np.testing.assert_array_equal(np.asarray(active), np.asarray(ref.active))
            np.testing.assert_array_equal(np.asarray(pos), np.asarray(ref.pos))


def test_distributed_full_masks_bitwise_equal_unmasked(setup):
    """All-True masks reproduce the pre-mask step exactly: positions equal
    the unmasked uniform-neighbor hop under the same key."""
    g, pcfg, mesh, step = setup
    nbrs, degs = jnp.asarray(g.neighbors), jnp.asarray(g.degrees)
    node_up, edge_up = _full_masks(g)
    key = jax.random.key(5)
    pos, active, track, last_seen, hist, total = _init(g, pcfg, key)
    t = jnp.int32(0)
    with mesh:
        for _ in range(5):
            ws = wlk.WalkState(pos=pos, active=active, track=track)
            ref = wlk.move_walks(ws, nbrs, degs, fold_in_time(key, t, 0))
            t, pos, active, track, last_seen, hist, total, key, z = step(
                t, pos, active, track, last_seen, hist, total, key, nbrs, degs,
                node_up, edge_up,
            )
            np.testing.assert_array_equal(np.asarray(pos), np.asarray(ref.pos))
