"""Reproduction of "Dynamic Loop Fusion in High-Level Synthesis" grown
into a jax_pallas system.

Layers (DESIGN.md §1): the paper's compiler and cycle-level DU
simulator (``repro.core``), static dependence analyses
(``repro.analysis``), batched design-space sweeps over the simulator
(``repro.dse``), and the wave executor with its device kernels
(``repro.core.executor``, ``repro.kernels``).
"""
