"""Walk-slot machinery edge cases: capacity overflow, slot reuse, identity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import walkers as wlk
from repro.core.estimator import NEVER


def _state(pos, active, track=None):
    pos = jnp.asarray(pos, jnp.int32)
    active = jnp.asarray(active, bool)
    if track is None:
        track = jnp.arange(pos.shape[0], dtype=jnp.int32)
    return wlk.WalkState(pos=pos, active=active, track=jnp.asarray(track, jnp.int32))


def test_fork_overflow_dropped_not_corrupted():
    """More fork events than free slots: extras drop, nothing is clobbered."""
    ws = _state([4, 5, 6, 7, 0, 0], [True, True, True, True, False, False])
    last_seen = jnp.full((8, 6), 3, jnp.int32)
    ev = jnp.asarray([True, True, True, True, False, False])  # 4 events, 2 free
    new_ws, new_ls, n, fp = wlk.execute_forks(ws, last_seen, ev, ws.pos, None, jnp.int32(9))
    assert int(n) == 2
    assert np.asarray(new_ws.active).all()  # exactly filled to capacity
    # free slots were matched to events in rank order: slot 4 <- walk 0, slot 5 <- walk 1
    assert int(new_ws.pos[4]) == 4 and int(new_ws.pos[5]) == 5
    np.testing.assert_array_equal(np.asarray(fp), [-1, -1, -1, -1, 0, 1])
    # surviving walks untouched
    np.testing.assert_array_equal(np.asarray(new_ws.pos[:4]), [4, 5, 6, 7])
    np.testing.assert_array_equal(np.asarray(new_ws.track[:4]), [0, 1, 2, 3])
    # dropped events (walks 2, 3) left no trace anywhere in last_seen
    ls = np.asarray(new_ls)
    assert (ls[:, :4] == 3).all()


def test_fork_with_zero_free_slots_is_noop():
    ws = _state([1, 2, 3], [True, True, True])
    last_seen = jnp.full((4, 3), 5, jnp.int32)
    ev = jnp.asarray([True, True, True])
    new_ws, new_ls, n, fp = wlk.execute_forks(ws, last_seen, ev, ws.pos, None, jnp.int32(7))
    assert int(n) == 0
    np.testing.assert_array_equal(np.asarray(new_ws.pos), np.asarray(ws.pos))
    np.testing.assert_array_equal(np.asarray(new_ws.active), np.asarray(ws.active))
    np.testing.assert_array_equal(np.asarray(new_ws.track), np.asarray(ws.track))
    assert (np.asarray(new_ls) == 5).all()
    assert (np.asarray(fp) == -1).all()


def test_decafork_slot_reuse_clears_stale_column():
    """Terminate a walk, fork into its slot: the stale last_seen column of
    the dead identity must not leak into the new walk's return stats."""
    ws = _state([2, 3, 1], [True, True, True])
    # slot 1's identity was seen everywhere at t=6 (stale once it dies)
    last_seen = jnp.asarray(
        [[0, 6, NEVER], [1, 6, NEVER], [2, 6, NEVER], [3, 6, NEVER]], jnp.int32
    )
    ws = wlk.execute_terminations(ws, jnp.asarray([False, True, False]))
    assert not bool(ws.active[1])
    ev = jnp.asarray([True, False, False])  # walk 0 (at node 2) forks
    new_ws, new_ls, n, fp = wlk.execute_forks(ws, last_seen, ev, ws.pos, None, jnp.int32(9))
    assert int(n) == 1 and bool(new_ws.active[1])
    assert int(new_ws.track[1]) == 1  # fresh identity = reused slot index
    ls = np.asarray(new_ls)
    # stale t=6 entries for the dead identity are gone ...
    assert ls[2, 1] == 9  # ... replaced by the fork origin's sighting at t
    np.testing.assert_array_equal(ls[[0, 1, 3], 1], [NEVER, NEVER, NEVER])
    # unrelated columns untouched
    np.testing.assert_array_equal(ls[:, 0], [0, 1, 2, 3])
    assert (ls[:, 2] == NEVER).all()


def test_missingperson_replacement_inherits_track():
    """MISSINGPERSON replacements carry the replaced walk's identity and
    keep its last_seen history (the whole point of the timeout rule)."""
    ws = _state([4, 0, 0], [True, False, False], track=[0, 1, 2])
    last_seen = jnp.asarray(
        [[7, 2, NEVER], [7, 2, NEVER], [7, 2, NEVER], [7, 2, NEVER], [7, 2, NEVER]],
        jnp.int32,
    )
    # walk 0 declares ids 1 and 2 missing -> two replacement forks from node 4
    ev = jnp.asarray([False, True, True])
    origins = jnp.asarray([4, 4, 4], jnp.int32)
    tracks = jnp.asarray([0, 1, 2], jnp.int32)
    parents = jnp.asarray([0, 0, 0], jnp.int32)
    new_ws, new_ls, n, fp = wlk.execute_forks(
        ws, last_seen, ev, origins, tracks, jnp.int32(12), parents
    )
    assert int(n) == 2
    np.testing.assert_array_equal(np.asarray(new_ws.active), [True, True, True])
    np.testing.assert_array_equal(np.asarray(new_ws.track), [0, 1, 2])
    np.testing.assert_array_equal(np.asarray(new_ws.pos), [4, 4, 4])
    np.testing.assert_array_equal(np.asarray(fp), [-1, 0, 0])
    # history untouched: replacements REUSE the replaced id's statistics
    np.testing.assert_array_equal(np.asarray(new_ls), np.asarray(last_seen))


def test_forks_execute_inside_jit_and_vmap():
    """The slot machinery stays shape-stable under jit+vmap (sweep path)."""

    def fork_once(key):
        pos = jax.random.randint(key, (6,), 0, 4, dtype=jnp.int32)
        ws = wlk.WalkState(
            pos=pos,
            active=jnp.asarray([True, True, True, False, False, False]),
            track=jnp.arange(6, dtype=jnp.int32),
        )
        ls = jnp.full((4, 6), 2, jnp.int32)
        ev = jnp.asarray([True, False, True, False, False, False])
        new_ws, new_ls, n, fp = wlk.execute_forks(ws, ls, ev, ws.pos, None, jnp.int32(5))
        return n, jnp.sum(new_ws.active)

    n, z = jax.jit(jax.vmap(fork_once))(jax.random.split(jax.random.key(0), 3))
    np.testing.assert_array_equal(np.asarray(n), [2, 2, 2])
    np.testing.assert_array_equal(np.asarray(z), [5, 5, 5])


# ---------------------------------------------------------------------------
# MISSINGPERSON event grid: slot-side allocation == event-side allocation
# ---------------------------------------------------------------------------


def _flat_grid_forks(ws, last_seen, ev):
    """``execute_forks`` on the flattened (W, C) grid: event e forks walk
    e // C with identity e % C from the parent's node."""
    W, C = ev.shape
    e = jnp.arange(W * C, dtype=jnp.int32)
    return wlk.execute_forks(
        ws, last_seen, ev.reshape(-1), ws.pos[e // C], e % C, jnp.int32(11), e // C
    )


def _grid_case(seed, W, C, n_active, density, zoo=False):
    """A walk state with ``n_active`` random slots live and a (W, C) event
    grid of the given density."""
    rng = np.random.default_rng(seed)
    active = np.zeros(W, bool)
    active[rng.permutation(W)[:n_active]] = True
    n = 12
    ws = wlk.WalkState(
        pos=jnp.asarray(rng.integers(0, n, W), jnp.int32),
        active=jnp.asarray(active),
        track=jnp.asarray(rng.integers(0, C, W), jnp.int32),
        prev=jnp.asarray(rng.integers(0, n, W), jnp.int32) if zoo else None,
        bloom=jnp.asarray(rng.random((W, 16)) < 0.5) if zoo else None,
    )
    last_seen = jnp.asarray(rng.integers(-5, 9, (n, C)), jnp.int32)
    ev = jnp.asarray(rng.random((W, C)) < density)
    return ws, last_seen, ev


def _last_cell_case():
    ws, ls, ev = _grid_case(5, 8, 8, 5, 0.0)
    return ws, ls, ev.at[-1, -1].set(True).at[-1, 3].set(True).at[2, -1].set(True)


GRID_CASES = {
    "no_events": lambda: _grid_case(0, 8, 8, 3, 0.0),
    "fewer_events_than_free": lambda: _grid_case(1, 8, 8, 2, 0.05),
    "overflow": lambda: _grid_case(2, 8, 8, 3, 0.4),
    "no_free_slot": lambda: _grid_case(3, 8, 8, 8, 0.5),
    "last_row_and_column": _last_cell_case,
    "c_ne_w": lambda: _grid_case(4, 8, 5, 4, 0.1),
    "zoo_prev_bloom": lambda: _grid_case(6, 8, 8, 3, 0.08, zoo=True),
}
# what each case promises, as (events, free slots) -> bool
GRID_SHAPES = {
    "no_events": lambda e, f: e == 0 < f,
    "fewer_events_than_free": lambda e, f: 0 < e < f,
    "overflow": lambda e, f: e > f > 0,
    "no_free_slot": lambda e, f: f == 0 < e,
    "last_row_and_column": lambda e, f: e == f == 3,
    "c_ne_w": lambda e, f: 0 < e,
    "zoo_prev_bloom": lambda e, f: 0 < e <= f,
}


def _assert_same_forks(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("case", [*GRID_CASES, "jit_vmap_seeds"])
def test_grid_forks_match_flattened_execute_forks(case):
    """``execute_grid_forks`` allocates slot-side; ``execute_forks`` on the
    flattened grid event-side. Both give the same state, ``last_seen``,
    fork count and ``fork_parent``, bit for bit."""
    if case == "jit_vmap_seeds":
        cases = [_grid_case(s, 16, 16, s % 17, d)
                 for s, d in enumerate([0.0, 0.02, 0.1, 0.3, 0.02, 1.0])]
        args = jax.tree.map(lambda *x: jnp.stack(x), *cases)
        want = jax.jit(jax.vmap(_flat_grid_forks))(*args)
        got = jax.jit(jax.vmap(wlk.execute_grid_forks))(*args)
    else:
        args = GRID_CASES[case]()
        assert GRID_SHAPES[case](int(jnp.sum(args[2])), int(jnp.sum(~args[0].active)))
        want = _flat_grid_forks(*args)
        got = wlk.execute_grid_forks(*args)
    _assert_same_forks(got, want)
    n_ev = jnp.sum(args[2], axis=(-2, -1))
    np.testing.assert_array_equal(
        np.asarray(want[2]), np.minimum(n_ev, jnp.sum(~args[0].active, axis=-1))
    )


# ---------------------------------------------------------------------------
# masked movement edge cases (the fused round's hop shares these paths)
# ---------------------------------------------------------------------------


def test_walk_holds_position_when_every_incident_edge_is_down():
    """A walk on a node whose incident edges are ALL down must hold
    position (not teleport, not die) — on both hop implementations."""
    # a triangle: every node has degree 2
    neighbors = jnp.asarray([[1, 2], [0, 2], [0, 1]], jnp.int32)
    degrees = jnp.asarray([2, 2, 2], jnp.int32)
    ws = _state([0, 1], [True, True])
    avail = jnp.asarray(
        [[False, False], [True, True], [True, True]]
    )  # node 0 isolated
    key = jax.random.key(3)
    moved = wlk.move_walks(ws, neighbors, degrees, key, avail)
    assert int(moved.pos[0]) == 0  # stranded walk held position
    assert int(moved.pos[1]) in (0, 2)  # free walk moved
    # row-restricted variant agrees bitwise (same uniforms)
    u = jax.random.uniform(key, (2,))
    got = wlk.move_walks_rows(
        ws, neighbors[ws.pos], u, avail[ws.pos], degrees.dtype
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(moved.pos))


def test_select_available_edge_zero_count_rank_select():
    """adeg == 0 rows: the returned count is 0 (callers hold position);
    the selected index stays in-bounds garbage, never out of range."""
    row_mask = jnp.asarray(
        [[False, False, False], [True, False, True], [False, True, False]]
    )
    u = jnp.asarray([0.99, 0.99, 0.0])
    adeg, sel = wlk.select_available_edge(row_mask, u, jnp.int32)
    np.testing.assert_array_equal(np.asarray(adeg), [0, 2, 1])
    assert 0 <= int(sel[0]) < 3  # garbage but in-bounds
    assert int(sel[1]) == 2  # u=0.99 over 2 available -> rank 1 -> slot 2
    assert int(sel[2]) == 1  # u=0.0 -> rank 0 -> the only available slot


def test_degree_one_node_under_link_churn():
    """A walk on a degree-1 node: moves over its single edge while the
    link is up, holds position while it is down, resumes after recovery
    — the fused and unfused hops agree at every phase."""
    # path graph 0 - 1 - 2; node 0 has degree 1 (padded slot at col 1)
    neighbors = jnp.asarray([[1, 0], [0, 2], [1, 0]], jnp.int32)
    degrees = jnp.asarray([1, 2, 1], jnp.int32)
    ws = _state([0], [True])
    key = jax.random.key(9)
    u = jax.random.uniform(key, (1,))
    for edge_up, want in [(True, 1), (False, 0), (True, 1)]:
        avail = jnp.asarray([[edge_up, False], [edge_up, True], [True, False]])
        moved = wlk.move_walks(ws, neighbors, degrees, key, avail)
        assert int(moved.pos[0]) == want, f"edge_up={edge_up}"
        got = wlk.move_walks_rows(
            ws, neighbors[ws.pos], u, avail[ws.pos], degrees.dtype
        )
        assert int(got[0]) == want, f"rows, edge_up={edge_up}"
