"""Instances and plain reference for the ``bfs_rmat`` configuration.

Numpy only: nothing here imports the system under test.

An instance is one Graph500 kernel 2 search (specification section 4)
on the undirected graph kernel 1 builds from a Kronecker edge list
(section 3), the same graphs as the ``pagerank_rmat`` configuration: the
edge list and kernel 1's CSR build are that configuration's
(``pagerank_rmat_ref.kronecker_edges`` and ``undirected``). The edge list
is drawn from a ``base`` generator; the vertex labels and the search key
from ``rng``. The search key is drawn uniformly among the vertices of
degree at least 1, as the specification samples them.

Vertex ids and -1 are held in float64, exactly. The search starts with
``parent[root] = root``, every other parent -1, ``queue[0] = root`` and
``foff`` all 0. ``foff`` has room for the most levels a search can have
(``n + 1`` offsets), so every instance of a scale has the same arrays'
sizes. ``levels`` is the number of non-empty BFS levels plus one: the
last level finds its frontier empty, as the specification's "until the
frontier is empty" loop does.

The reference is a plain sequential queue BFS that runs until the
frontier is empty: frontier vertices in queue order, each row's
neighbours in CSR (sorted) order, a neighbour's parent set by its first
discoverer, and ``foff[t + 1]`` the queue's length when level ``t``
starts.
"""

from __future__ import annotations

import importlib.util
import pathlib

import numpy as np

PROTECTED = ("parent", "queue", "foff")


def _graph500():
    """``pagerank_rmat_ref``, the Kronecker generator and kernel 1."""
    path = pathlib.Path(__file__).with_name("pagerank_rmat_ref.py")
    spec = importlib.util.spec_from_file_location("_bfs_rmat_graph500", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


graph500 = _graph500()


def csr(params, rng, base):
    """(rp, cidx) of kernel 1's graph from the edge list of ``base``,
    under a labelling drawn from ``rng``."""
    n = 1 << params["scale"]
    start, end = graph500.kronecker_edges(
        params["scale"], params["edgefactor"],
        params["a"], params["b"], params["c"], base,
    )
    label = rng.permutation(n)
    row, nbr = graph500.undirected(label[start], label[end], n)
    rp = np.zeros(n + 1, dtype=np.int64)
    rp[1:] = np.cumsum(np.bincount(row, minlength=n))
    return rp, nbr.astype(np.int64)


def search(rp, cidx, root):
    """(non-empty levels, mask of reached vertices) of a BFS from
    ``root``."""
    seen = np.zeros(len(rp) - 1, dtype=bool)
    seen[root] = True
    front, levels = np.array([root]), 0
    while len(front):
        levels += 1
        nbrs = np.concatenate([cidx[rp[u]:rp[u + 1]] for u in front])
        front = np.unique(nbrs[~seen[nbrs]])
        seen[front] = True
    return levels, seen


def generate(params, rng, base):
    """One instance: (arrays, program params)."""
    n = 1 << params["scale"]
    rp, cidx = csr(params, rng, base)
    root = int(rng.choice(np.flatnonzero(np.diff(rp) > 0)))
    levels = search(rp, cidx, root)[0] + 1
    parent = np.full(n, -1.0)
    parent[root] = root
    queue = np.full(n, -1.0)
    queue[0] = root
    arrays = {
        "parent": parent,
        "queue": queue,
        "foff": np.zeros(n + 1),
        "rp": rp,
        "cidx": cidx,
    }
    return arrays, {"levels": levels}


def reference(arrays, params, dtype=np.float64):
    """Final protected arrays of sequential execution, held in ``dtype``
    (float64 is the configuration's precision)."""
    rp, cidx = arrays["rp"], arrays["cidx"]
    parent = arrays["parent"].astype(dtype)
    queue = arrays["queue"].astype(dtype)
    foff = arrays["foff"].astype(dtype)
    t, tail = 0, 1
    while True:
        foff[t + 1] = tail
        if foff[t + 1] == foff[t]:
            break
        for k in range(int(foff[t]), int(foff[t + 1])):
            u = int(queue[k])
            for v in cidx[rp[u]:rp[u + 1]]:
                if parent[v] < 0:
                    parent[v] = u
                    queue[tail] = v
                    tail += 1
        t += 1
    return {"parent": parent, "queue": queue, "foff": foff}


def words(arrays, params):
    """Float64 words the sequential program reads or writes in protected
    arrays: per level one ``foff`` store and two loads; per frontier
    vertex one ``queue`` load; per scanned edge one ``parent`` load; per
    discovered vertex one ``parent`` and one ``queue`` store."""
    rp = arrays["rp"]
    nonempty, reached = search(rp, arrays["cidx"], int(arrays["queue"][0]))
    r = int(reached.sum())
    return 3 * (nonempty + 1) + r + int(np.diff(rp)[reached].sum()) \
        + 2 * (r - 1)
