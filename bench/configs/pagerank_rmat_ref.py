"""Instances and plain reference for the ``pagerank_rmat`` configuration.

Numpy only: nothing here imports the system under test.

An instance is the graph that Graph500 kernel 1 builds from one
Kronecker edge list (Graph500 specification, section 3 "Generating the
edge list": R-MAT initiator A/B/C/D = 0.57/0.19/0.19/0.05, edgefactor 16,
vertex labels and edge order permuted; kernel 1: the graph is
undirected). Each edge is taken in both directions, and self-loops and
duplicate edges are removed, as the reference implementation's CSR
build does. It is laid out as pull-CSR for the Table-1 PageRank: row
``v`` lists the neighbours of ``v``, sorted, and ``invdeg[u]`` is 1 over
the degree of ``u``. The edge list is drawn from a ``base`` generator
and the vertex labels from ``rng``: instances of one base are the same
graph under a new labelling, so every run can get the same set of
graphs.

The reference runs the same iterations with the same float64 operations
in the same order as sequential execution of the program: contributions,
then each row's neighbours accumulated into ``acc`` in CSR order
(``np.add.at`` applies its updates one by one, in index order), then
``rank = acc * damping + teleport``.
"""

from __future__ import annotations

import numpy as np

PROTECTED = ("rank", "contrib", "acc")


def kronecker_edges(scale, edgefactor, a, b, c, rng):
    """(start, end) vertex arrays of one Graph500 Kronecker edge list."""
    n = 1 << scale
    m = edgefactor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ij = np.zeros((2, m), dtype=np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        ij[0] += ii.astype(np.int64) << bit
        ij[1] += jj.astype(np.int64) << bit
    ij = rng.permutation(n)[ij]
    ij = ij[:, rng.permutation(m)]
    return ij[0], ij[1]


def undirected(start, end, n):
    """(row, neighbour) pairs of kernel 1's undirected graph, sorted by
    row, then neighbour: every edge in both directions, self-loops and
    duplicates removed."""
    keep = start != end
    row = np.concatenate([start[keep], end[keep]])
    nbr = np.concatenate([end[keep], start[keep]])
    key = np.unique(row * n + nbr)
    return key // n, key % n


def generate(params, rng, base):
    """One instance: (arrays, program params); the edge list comes from
    ``base``, the labels from ``rng``."""
    n = 1 << params["scale"]
    start, end = kronecker_edges(
        params["scale"], params["edgefactor"],
        params["a"], params["b"], params["c"], base,
    )
    label = rng.permutation(n)
    row, nbr = undirected(label[start], label[end], n)
    deg = np.bincount(row, minlength=n)
    rp = np.zeros(n + 1, dtype=np.int64)
    rp[1:] = np.cumsum(deg)
    arrays = {
        "rank": np.full(n, 1.0 / n),
        "contrib": np.zeros(n),
        "acc": np.zeros(n),
        "rp": rp,
        "cidx": nbr.astype(np.int64),
        "invdeg": 1.0 / np.maximum(deg, 1).astype(np.float64),
    }
    return arrays, {"iters": params["iterations"], "nodes": n}


def reference(arrays, params, dtype=np.float64):
    """Final protected arrays of sequential execution, computed in
    ``dtype`` (float64 is the configuration's precision)."""
    n = len(arrays["rank"])
    dst = np.repeat(np.arange(n), np.diff(arrays["rp"]))
    cidx = arrays["cidx"]
    invdeg = arrays["invdeg"].astype(dtype)
    damping = dtype(params["damping"])
    teleport = dtype(params["teleport"])
    rank = arrays["rank"].astype(dtype)
    contrib = arrays["contrib"].astype(dtype)
    acc = arrays["acc"].astype(dtype)
    for _ in range(params["iterations"]):
        contrib = rank * invdeg
        acc = np.zeros(n, dtype=dtype)
        np.add.at(acc, dst, contrib[cidx])
        rank = acc * damping + teleport
    return {"rank": rank, "contrib": contrib, "acc": acc}


def words(arrays, params):
    """Float64 words the sequential program reads or writes in protected
    arrays: per iteration 3 per vertex in the contribution loop, 3 per
    edge in the gather loop, 2 per vertex in the update loop."""
    n = len(arrays["rank"])
    e = len(arrays["cidx"])
    return params["iterations"] * (5 * n + 3 * e)
