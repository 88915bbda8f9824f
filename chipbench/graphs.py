"""Graphs the benchmark hands to the program, made from the configuration.

The benchmark makes its own inputs, so the reference and the program walk
the same adjacency and neither takes it from the other.
"""
from __future__ import annotations

from collections import deque

import numpy as np


def random_regular(n: int, degree: int, seed: int) -> np.ndarray:
    """(n, degree) int32 neighbour table of a connected simple
    ``degree``-regular graph, rows sorted.

    Starts from the circulant graph (i joined to i±1..i±degree/2) and
    applies 20 random double-edge swaps per edge; a swap that would make
    a loop or a multi-edge is skipped. The swap chain keeps every degree
    and mixes towards the uniform random regular graph.
    """
    if degree % 2 or degree >= n:
        raise ValueError("need an even degree below n")
    rng = np.random.default_rng(seed)
    edges = {
        (min(i, (i + k) % n), max(i, (i + k) % n))
        for i in range(n)
        for k in range(1, degree // 2 + 1)
    }
    adj = [set() for _ in range(n)]
    for a, b in edges:
        adj[a].add(b)
        adj[b].add(a)
    edge_list = sorted(edges)
    for _ in range(20 * len(edge_list)):
        i, j = rng.integers(len(edge_list), size=2)
        (a, b), (c, d) = edge_list[i], edge_list[j]
        if rng.random() < 0.5:
            c, d = d, c
        # (a,b),(c,d) -> (a,d),(c,b)
        if len({a, b, c, d}) < 4 or d in adj[a] or b in adj[c]:
            continue
        adj[a].remove(b), adj[b].remove(a), adj[c].remove(d), adj[d].remove(c)
        adj[a].add(d), adj[d].add(a), adj[c].add(b), adj[b].add(c)
        edge_list[i] = (min(a, d), max(a, d))
        edge_list[j] = (min(c, b), max(c, b))
    nbrs = np.array([sorted(s) for s in adj], np.int32)
    if not connected(nbrs):
        raise ValueError(f"seed {seed} gave a disconnected graph")
    return nbrs


def connected(nbrs: np.ndarray) -> bool:
    seen = {0}
    todo = deque([0])
    while todo:
        for j in nbrs[todo.popleft()]:
            if int(j) not in seen:
                seen.add(int(j))
                todo.append(int(j))
    return len(seen) == nbrs.shape[0]


def make(spec: dict) -> np.ndarray:
    """The neighbour table a configuration's ``graph`` entry describes."""
    if spec["family"] != "random_regular":
        raise ValueError(f"unknown graph family {spec['family']!r}")
    return random_regular(spec["n"], spec["degree"], spec["seed"])
