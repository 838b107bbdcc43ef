"""RunConfig unification contract (DESIGN.md §13; ISSUE: api_redesign).

Pins, in order:

  * config-vs-kwargs bit-identity: ``simulate(config=RunConfig(...))``
    equals the legacy-kwarg spelling (cycles, arrays) across a
    kernel x mode x engine sample, and ``executor.execute`` likewise;
  * ``result_key`` derivation: ``SweepPoint.result_key`` equals
    ``dse.result_projection`` of the point's config — one projection;
  * conflict behavior: an explicit kwarg disagreeing with an explicit
    config raises ``ConfigConflict`` (and agreement passes through);
  * cache-key coverage: every RunConfig field either moves
    ``result_projection``'s output or is listed in
    ``RESULT_INERT_FIELDS`` with its inertness proof obligation;
  * vocabulary drift: the dependency-free ``core.config`` value tuples
    match their canonical homes (``dae.PREDICTORS`` etc.);
  * the ``dse.sweep(validate=)`` -> ``differential=`` deprecation shim.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import dae as daelib
from repro.core import executor
from repro.core import programs
from repro.core import schedule as schedlib
from repro.core import simulator
from repro.core.config import (
    ConfigConflict,
    RunConfig,
    PREDICTORS,
    TRACE_MODES,
    resolve,
)
from repro.core.simulator import SimParams
from repro import dse
from repro.dse.spec import RESULT_INERT_FIELDS, result_projection

SCALE = {
    "RAWloop": 64, "WARloop": 64, "WAWloop": 64, "hist+add": 48,
    "tanh+spmv": 32, "bnn": 16, "pagerank": 24, "fft": 32, "matpower": 16,
}


def _run(kernel, **kw):
    b = programs.get(kernel)
    prog, arrays, params = b.make(SCALE[kernel])
    return simulator.simulate(
        prog, {k: v.copy() for k, v in arrays.items()}, params, **kw
    )


# -- bit-identity ------------------------------------------------------------


@pytest.mark.parametrize("kernel", ("RAWloop", "hist+add", "tanh+spmv"))
@pytest.mark.parametrize("mode,engine", [
    ("STA", "event"), ("LSQ", "event"), ("FUS2", "event"), ("FUS2", "cycle"),
])
def test_simulate_config_bit_identical_to_kwargs(kernel, mode, engine):
    legacy = _run(kernel, mode=mode, engine=engine, trace_mode="auto")
    cfg = _run(kernel, config=RunConfig(mode=mode, engine=engine))
    assert legacy.cycles == cfg.cycles
    assert legacy.dram_bursts == cfg.dram_bursts
    assert set(legacy.arrays) == set(cfg.arrays)
    for k in legacy.arrays:
        assert np.array_equal(legacy.arrays[k], cfg.arrays[k])


def test_simulate_config_every_registered_kernel():
    """Acceptance pin: config spelling is bit-identical on every
    registered kernel (default FUS2/event point; speculative kernels
    run under speculation="auto")."""
    for name, bench in sorted(programs.REGISTRY.items()):
        scale = SCALE.get(name, max(bench.default_scale // 32, 8))
        prog, arrays, params = bench.make(scale)
        spec_knob = "auto" if bench.speculative else "off"
        legacy = simulator.simulate(
            prog, {k: v.copy() for k, v in arrays.items()}, params,
            mode="FUS2", speculation=spec_knob,
        )
        cfg = simulator.simulate(
            prog, {k: v.copy() for k, v in arrays.items()}, params,
            config=RunConfig(speculation=spec_knob),
        )
        assert legacy.cycles == cfg.cycles, name
        for k in legacy.arrays:
            assert np.array_equal(legacy.arrays[k], cfg.arrays[k]), name


def test_execute_config_bit_identical_to_kwargs():
    b = programs.get("hist+add")
    prog, arrays, params = b.make(48)
    legacy = executor.execute(
        prog, {k: v.copy() for k, v in arrays.items()}, params,
        fifo_depth=2, batch_waves=False,
    )
    cfg = executor.execute(
        prog, {k: v.copy() for k, v in arrays.items()}, params,
        config=RunConfig(fifo_depth=2, batch_waves=False),
    )
    for k in legacy.arrays:
        assert np.array_equal(legacy.arrays[k], cfg.arrays[k])
    assert legacy.waves.tolist() == cfg.waves.tolist()


def test_config_sim_overrides_flow_into_simparams():
    """config.fifo_depth/fifo_latency/spec_runahead act exactly like
    the matching sim= override."""
    via_sim = _run(
        "tanh+spmv", mode="FUS2",
        sim=SimParams(fifo_depth=2, fifo_latency=3),
    )
    via_cfg = _run(
        "tanh+spmv", config=RunConfig(fifo_depth=2, fifo_latency=3),
    )
    assert via_sim.cycles == via_cfg.cycles
    assert via_sim.fifo_stats == via_cfg.fifo_stats


# -- conflicts ---------------------------------------------------------------


def test_conflicting_kwarg_raises():
    with pytest.raises(ConfigConflict):
        _run("RAWloop", mode="STA", config=RunConfig(mode="FUS2"))
    prog, arrays, params = programs.get("RAWloop").make(32)
    with pytest.raises(ConfigConflict):
        executor.execute(
            prog, arrays, params, backend="pallas",
            config=RunConfig(backend="numpy"),
        )
    with pytest.raises(ConfigConflict):
        executor.build_wave_plan(
            prog, arrays, params, fifo_depth=8,
            config=RunConfig(fifo_depth=2),
        )


def test_agreeing_kwarg_passes():
    res = _run("RAWloop", mode="STA", config=RunConfig(mode="STA"))
    assert res.cycles == _run("RAWloop", mode="STA").cycles


def test_conflicting_sim_field_raises():
    with pytest.raises(ConfigConflict):
        _run(
            "tanh+spmv", sim=SimParams(fifo_depth=3),
            config=RunConfig(fifo_depth=2),
        )
    # sim left at default: config wins, no conflict
    res = _run(
        "tanh+spmv", sim=SimParams(), config=RunConfig(fifo_depth=2),
    )
    assert res.cycles == _run("tanh+spmv", config=RunConfig(fifo_depth=2)).cycles


def test_sweepspec_config_axis_conflict():
    with pytest.raises(ConfigConflict):
        dse.SweepSpec(
            kernels=("RAWloop",), modes=("STA",),
            config=RunConfig(mode="LSQ"),
        ).points()
    # defaulted axes collapse to the config's values
    pts = dse.SweepSpec(
        kernels=("RAWloop",), scales={"RAWloop": 32},
        config=RunConfig(mode="STA", engine="cycle"),
    ).points()
    assert len(pts) == 1
    assert pts[0].mode == "STA" and pts[0].engine == "cycle"


def test_invalid_values_rejected():
    with pytest.raises(ValueError):
        RunConfig(mode="FUS3")
    with pytest.raises(ValueError):
        RunConfig(predictor="psychic")
    with pytest.raises(ValueError):
        RunConfig(fifo_depth=0)
    with pytest.raises(TypeError):
        resolve("FUS2")  # config= must be a RunConfig


# -- result-key derivation ---------------------------------------------------


def test_result_key_delegates_to_projection():
    for pt in dse.SweepSpec(
        kernels=("RAWloop", "bnn"), scales={"RAWloop": 32, "bnn": 16},
        modes=("STA", "FUS2"), speculations=("off", "auto"),
        sizings={"base": {}, "deep": {"sta_mem_dep_ii": 99, "fifo_depth": 2}},
    ).points():
        assert pt.result_key == result_projection(
            pt.kernel, pt.scale, pt.config, pt.sim
        )


def test_every_config_field_keyed_or_inert():
    """Every RunConfig field must either move result_projection's
    output in some context, or be declared inert in
    RESULT_INERT_FIELDS — no third category, no silent drift when a
    field is added."""
    kernel, scale = "chase_sum", 32  # speculative kernel: all classes live
    assert programs.REGISTRY[kernel].speculative
    base = RunConfig(mode="FUS2", speculation="auto")
    # a non-default probe value per field
    probes = {
        "mode": "LSQ", "engine": "cycle", "trace_mode": "interp",
        "speculation": "off", "predictor": "stride", "spec_runahead": 3,
        "fifo_depth": 2, "fifo_latency": 5, "static_prune": True,
        "validate_hints": True, "backend": "pallas", "batch_waves": False,
        "symbolic_admission": False,
    }
    fields = {f.name for f in dataclasses.fields(RunConfig)}
    assert set(probes) == fields, "probe table out of date"
    keyed, inert = set(), set()
    ref = result_projection(kernel, scale, base)
    for name, probe in probes.items():
        mutated = dataclasses.replace(base, **{name: probe})
        if result_projection(kernel, scale, mutated) != ref:
            keyed.add(name)
        else:
            inert.add(name)
    assert inert == set(RESULT_INERT_FIELDS), (
        f"inert-field drift: projection says {sorted(inert)}, "
        f"RESULT_INERT_FIELDS says {sorted(RESULT_INERT_FIELDS)}"
    )
    assert keyed == fields - set(RESULT_INERT_FIELDS)


# -- vocabulary drift --------------------------------------------------------


def test_config_vocabularies_match_canonical_homes():
    assert PREDICTORS == daelib.PREDICTORS
    assert TRACE_MODES == schedlib.TRACE_MODES
    from repro.dse import spec as dsespec

    assert set(dsespec.MODES) == {"STA", "LSQ", "FUS1", "FUS2"}


# -- deprecation shim --------------------------------------------------------


def test_sweep_validate_deprecated_shim():
    spec = [dse.SweepPoint("RAWloop", 32, mode="FUS2")]
    with pytest.warns(DeprecationWarning, match="differential"):
        old = dse.sweep(spec, validate=True)
    new = dse.sweep(spec, differential=True)
    assert old.points[0].result.cycles == new.points[0].result.cycles
