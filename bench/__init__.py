"""Chip benchmark of ``executor.execute`` (see ``BENCHMARK.json``).

Everything a cell needs is found by name: ``configs/<config>.json`` (the
deployment's sizes), ``configs/<config>.py`` (its LoopIR program),
``configs/<config>_ref.py`` (instances and the plain reference),
``traffic/<traffic>.json`` (the call mix) and ``metrics/<metric>.py`` (one
reader per metric).
"""
