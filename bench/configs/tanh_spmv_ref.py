"""Instances and plain reference for the ``tanh_spmv`` configuration.

Numpy only: nothing here imports the system under test.

An instance is the Table-1 ``tanh+spmv`` input on an HPCG operator: the
sparse matrix is HPCG's 27-point stencil on an ``nx * ny * nz`` grid
(HPCG reference ``GenerateProblem_ref.cpp``: rows in grid order
``x + nx * (y + ny * z)``, each row's neighbours within the grid in
ascending column order, 26 on the diagonal and -1 elsewhere), laid out as
sorted COO. ``level`` picks one operator of HPCG's multigrid hierarchy:
level ``L`` is the same stencil on the grid halved ``L`` times in each
dimension (``GenerateCoarseProblem``). The vector ``v`` is drawn from
``rng``, standard normal, so every call gets new values, and with them a
new set of guarded stores; the matrix is the operator's, the same in
every call, as in HPCG's own repeated products.

The reference applies ``tanh`` in place to the positive entries of ``v``
(the guarded store), then accumulates ``y[row] += val * v[col]`` over the
nonzeros in order (``np.add.at`` applies its updates one by one, in index
order), with the same float64 operations as sequential execution.
"""

from __future__ import annotations

import numpy as np

PROTECTED = ("v", "y")


def grid(params):
    """(nx, ny, nz) of the operator at ``params["level"]`` (0, the finest,
    where it is not given)."""
    level = params.get("level", 0)
    if not 0 <= level < params["mg_levels"]:
        raise ValueError(f"level {level} outside the {params['mg_levels']} levels")
    return tuple(params[k] >> level for k in ("nx", "ny", "nz"))


def sizes(params):
    """(rows, nonzeros) of the operator: a grid dimension ``d`` gives
    each row 3 neighbours in it, less one at each face, so ``3d - 2``
    pairs in all."""
    nx, ny, nz = grid(params)
    return nx * ny * nz, (3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2)


def stencil(params):
    """(rows, cols, vals) of the 27-point operator, in HPCG's order."""
    nx, ny, nz = grid(params)
    ix, iy, iz = np.meshgrid(np.arange(nx), np.arange(ny), np.arange(nz),
                             indexing="ij")
    # row-major over (z, y, x), then the offsets in HPCG's loop order
    # sz, sy, sx, which is ascending column order
    cx, cy, cz = (a.transpose(2, 1, 0).reshape(-1) for a in (ix, iy, iz))
    d = np.array([-1, 0, 1])
    sz, sy, sx = (a.reshape(-1) for a in np.meshgrid(d, d, d, indexing="ij"))
    x, y, z = cx[:, None] + sx, cy[:, None] + sy, cz[:, None] + sz
    inside = (x >= 0) & (x < nx) & (y >= 0) & (y < ny) & (z >= 0) & (z < nz)
    rows = np.broadcast_to(np.arange(nx * ny * nz)[:, None], inside.shape)
    cols = x + nx * (y + ny * z)
    rows, cols = rows[inside], cols[inside]
    vals = np.where(rows == cols, params["diagonal"], params["off_diagonal"])
    return rows.astype(np.int64), cols.astype(np.int64), vals.astype(np.float64)


def generate(params, rng, base):
    """One instance: (arrays, program params); ``v`` comes from ``rng``,
    the operator from ``params`` (``base`` fixes nothing here)."""
    rows, cols, vals = stencil(params)
    n = int(rows[-1]) + 1
    arrays = {
        "v": rng.standard_normal(n),
        "y": np.zeros(n),
        "rows": rows,
        "cols": cols,
        "val": vals,
    }
    return arrays, {"n": n, "nnz": len(rows)}


def reference(arrays, params, dtype=np.float64):
    """Final protected arrays of sequential execution, computed in
    ``dtype`` (float64 is the configuration's precision)."""
    v = arrays["v"].astype(dtype)
    y = arrays["y"].astype(dtype)
    pos = v > 0
    v[pos] = np.tanh(v[pos])
    np.add.at(y, arrays["rows"], arrays["val"].astype(dtype) * v[arrays["cols"]])
    return {"v": v, "y": y}


def words(arrays, params):
    """Float64 words the sequential program reads or writes in protected
    arrays: a load of every ``v`` entry, a store of each positive one, and
    per nonzero a load of ``v``, a load of ``y`` and a store of ``y``."""
    return len(arrays["v"]) + int(np.count_nonzero(arrays["v"] > 0)) \
        + 3 * len(arrays["val"])
