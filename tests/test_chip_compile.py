"""Compile rehearsals of the wave backend's device step for TPU v5e.

``kernels/wave_exec/kernel.wave_loop`` is lowered and compiled for one
chip of a described (not attached) v5e topology at the segment shapes
of real runs, so a step the TPU compiler refuses, or an image that no
longer fits the chip's 16 GB of HBM, fails here without a chip. A
compile that passes is not a chip run: nothing executes.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and every xdist worker
imports this file. Where no v5e topology can be described, the fixture
skips.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.wave_exec.kernel import wave_loop

V5E_HBM_BYTES = 16 * 10**9

# (image rows M incl. the scratch row, padded steps S, lane width W),
# taken from the plans chip_smoke.py runs
SHAPES = {
    # tanh+spmv at 2^18 rows: 524,288-word image, its widest segment
    "tanh+spmv@2^18-widest": (524_289, 1, 524_288),
    # bnn at 8x (66,049 rows): its longest segment, 128 padded steps
    "bnn@8x-longest": (66_049, 128, 512),
    # hist+add at 8x: 586 narrow steps, the longest segment padded to 1024
    "hist+add@8x": (97, 1024, 64),
    # 2^16 lanes over a 2^22-row image
    "w2^16-m2^22": (4_194_305, 16, 65_536),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape", list(SHAPES))
def test_wave_loop_compiles_for_v5e(one_chip, shape):
    m, s, w = SHAPES[shape]
    args = (
        jax.ShapeDtypeStruct((m, 2), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((s, w), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((s, w), jnp.bool_, sharding=one_chip),
        jax.ShapeDtypeStruct((s, w, 2), jnp.uint32, sharding=one_chip),
    )
    compiled = wave_loop.lower(*args).compile()
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    # the image and per-step tables are all the program holds: at least
    # their raw bytes, and within the chip's HBM
    raw = m * 2 * 4 + s * w * (4 + 1 + 8)
    assert raw <= need <= V5E_HBM_BYTES, (shape, need)
