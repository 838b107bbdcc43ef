#!/usr/bin/env python
"""Docs consistency gate (run by CI; see README "Tests").

Checks, failing loudly on the first broken invariant:

  1. every repo-relative path mentioned in README.md / DESIGN.md /
     ROADMAP.md (backtick-quoted or table-cell) exists,
  2. every ``DESIGN.md §N`` cross-reference used anywhere in the
     source tree or docs points at a section heading that exists,
  3. the public API surface the docs and examples lean on has real
     docstrings: every module/function/class named in PUBLIC_API, plus
     every module imported by ``examples/*.py`` from ``repro``,
  4. the CI gate table in README.md and the workflow agree in *both*
     directions: every job in the table exists in
     .github/workflows/ci.yml and every script the table claims a job
     runs is actually invoked there; conversely every workflow job is
     documented in the table and every benchmarks/ or tools/ script the
     workflow invokes is named somewhere in README/DESIGN — so a CI
     refactor cannot silently orphan a documented gate (or document a
     gate that no longer runs),
  5. the README "The knobs" table and ``repro.core.config.RunConfig``
     agree exactly: one table row per dataclass field (backticked field
     name in the first cell), no extra rows, no undocumented fields.

Usage:  python tools/check_docs.py   (repo root, PYTHONPATH-free)
"""

from __future__ import annotations

import ast
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
sys.path.insert(0, SRC)

DOCS = ("README.md", "DESIGN.md", "ROADMAP.md")

# (module, attribute or None) — the surface README/DESIGN/examples name
PUBLIC_API = [
    ("repro.core.simulator", "simulate"),
    ("repro.core.simulator", "simulate_traced"),
    ("repro.core.simulator", "Compiled"),
    ("repro.core.simulator", "SimParams"),
    ("repro.core.simulator", "SimResult"),
    ("repro.core.simulator", "SharedArtifacts"),
    ("repro.core.schedule", "compile_pe_trace"),
    ("repro.core.schedule", "trace_program"),
    ("repro.core.monotonic", "analyze_program"),
    ("repro.core.loopir", "interpret"),
    ("repro.core.loopir", "compile_walk"),
    ("repro.core.loopir", "Program"),
    ("repro.core.dae", "decouple"),
    ("repro.core.dae", "record_cu_script"),
    ("repro.core.dae", "ReplayCU"),
    ("repro.core.speculate", "SpecPlan"),
    ("repro.core.speculate", "trace_spec_pe"),
    ("repro.core.du", "check_pair_batch"),
    ("repro.core.config", "RunConfig"),
    ("repro.core.config", "resolve"),
    ("repro.core.executor", "execute"),
    ("repro.core.executor", "build_wave_plan"),
    ("repro.core.executor", "WavePlan"),
    ("repro.core.executor", "validate_plan"),
    ("repro.core.optable", "compile_store_tables"),
    ("repro.core.optable", "StoreTable"),
    ("repro.kernels.wave_exec", "run_plan"),
    ("repro.kernels.wave_exec", "run_sequential"),
    ("repro.core.programs", None),
    ("repro.analysis.deps", "certify_pairs"),
    ("repro.analysis.deps", "stream_facts"),
    ("repro.analysis.deps", "symbolically_free_ops"),
    ("repro.analysis.deps", "check_hint_stream"),
    ("repro.analysis.deps", "HintViolation"),
    ("repro.analysis.lint", "lint_program"),
    ("repro.analysis.lint", "Diagnostic"),
    ("repro.dse", "sweep"),
    ("repro.dse", "SweepSpec"),
    ("repro.dse", "iter_points"),
    ("repro.dse", "sweep_shard"),
    ("repro.dse", "merge_results"),
    ("repro.dse", "shard_plan"),
    ("repro.dse", "calibrate"),
    ("repro.dse.cache", "ResultCache"),
    ("repro.dse.cache", "SweepJournal"),
    ("repro.dse.spec", "result_projection"),
    ("repro.dse.summary", "sweep_speedups"),
    ("repro.dse.summary", "pareto_front"),
    ("repro.dse.summary", "ParetoTracker"),
]

errors: list[str] = []


def err(msg: str) -> None:
    errors.append(msg)
    print(f"FAIL: {msg}")


# -- 1. referenced paths exist ----------------------------------------------
# Docs name files the way the prose reads (`schedule.py`, `core/du.py`,
# `benchmarks/run.py`): a reference resolves if some repo file's path
# ends with it.

_PATH_RE = re.compile(r"`([A-Za-z0-9_./+-]+\.(?:py|md|json|yml|toml))`")

repo_files: set[str] = set()
for dirpath, dirs, files in os.walk(ROOT):
    dirs[:] = [d for d in dirs if d not in (".git", "__pycache__", ".dse_cache")]
    for fn in files:
        repo_files.add(os.path.relpath(os.path.join(dirpath, fn), ROOT))


def path_resolves(rel: str) -> bool:
    return any(f == rel or f.endswith("/" + rel) for f in repo_files)


for doc in DOCS:
    text = open(os.path.join(ROOT, doc)).read()
    for m in _PATH_RE.finditer(text):
        rel = m.group(1)
        if rel.startswith(("/", "~")) or "*" in rel:
            continue
        if not path_resolves(rel):
            err(f"{doc}: referenced path does not exist: {rel}")

# -- 2. DESIGN.md § cross-references resolve --------------------------------

design = open(os.path.join(ROOT, "DESIGN.md")).read()
sections = set()
for line in design.splitlines():
    m = re.match(r"#+\s+§?(\d+)(?:\.(\d+))?[.\s]", line)
    if m:
        sections.add(m.group(1) if m.group(2) is None else f"{m.group(1)}.{m.group(2)}")
ref_re = re.compile(r"DESIGN\.md\s+§(\d+(?:\.\d+)?)")


def scan_refs(path: str, text: str) -> None:
    for m in ref_re.finditer(text):
        sec = m.group(1)
        if sec not in sections and sec.split(".")[0] not in sections:
            err(f"{path}: dangling cross-reference DESIGN.md §{sec}")


for doc in DOCS:
    scan_refs(doc, open(os.path.join(ROOT, doc)).read())
for dirpath, _dirs, files in os.walk(SRC):
    for fn in files:
        if fn.endswith(".py"):
            p = os.path.join(dirpath, fn)
            scan_refs(os.path.relpath(p, ROOT), open(p).read())

# -- 4. CI gates: README table <-> workflow, both directions -----------------
# Parsed with regexes, not pyyaml — CI installs only jax/numpy/pytest/
# hypothesis and this gate must not grow a dependency.

WORKFLOW = os.path.join(ROOT, ".github", "workflows", "ci.yml")

_JOB_RE = re.compile(r"^  ([A-Za-z_][\w-]*):\s*$")
_SCRIPT_RE = re.compile(r"\b((?:benchmarks|tools|examples|tests)/[\w./-]+\.py)\b")


def parse_workflow(path: str) -> tuple[set[str], set[str]]:
    """(job ids, repo-relative scripts invoked by run: commands).

    Comments are stripped before harvesting scripts — a commented-out
    (or merely mentioned) gate must not satisfy the "workflow actually
    invokes it" direction of the check.
    """
    jobs: set[str] = set()
    scripts: set[str] = set()
    in_jobs = False
    for line in open(path):
        if re.match(r"^jobs:\s*$", line):
            in_jobs = True
            continue
        if in_jobs and re.match(r"^[A-Za-z_]", line):
            in_jobs = False  # left the jobs: mapping
        if in_jobs:
            m = _JOB_RE.match(line)
            if m:
                jobs.add(m.group(1))
        scripts.update(_SCRIPT_RE.findall(re.sub(r"#.*", "", line)))
    return jobs, scripts


def parse_gate_table(readme: str) -> list[tuple[str, set[str]]]:
    """Rows of the README "CI gates" table: (job id, scripts named)."""
    rows: list[tuple[str, set[str]]] = []
    in_section = False
    for line in readme.splitlines():
        if re.match(r"^#{2,}\s+CI gates", line):
            in_section = True
            continue
        if in_section and line.startswith("#"):
            break
        if in_section and line.startswith("|"):
            cells = [c.strip() for c in line.strip("|").split("|")]
            if len(cells) < 2 or set(cells[0]) <= {"-", " ", ":"}:
                continue
            job = cells[0].strip("`")
            if job.lower() in ("job", ""):
                continue
            scripts = set()
            for c in cells[1:]:
                scripts.update(_SCRIPT_RE.findall(c))
            rows.append((job, scripts))
    return rows


if not os.path.exists(WORKFLOW):
    err("no CI workflow at .github/workflows/ci.yml")
else:
    wf_jobs, wf_scripts = parse_workflow(WORKFLOW)
    readme_text = open(os.path.join(ROOT, "README.md")).read()
    design_text = open(os.path.join(ROOT, "DESIGN.md")).read()
    gate_rows = parse_gate_table(readme_text)
    if not gate_rows:
        err('README.md: no "CI gates" table (## CI gates section)')
    table_jobs = {job for job, _ in gate_rows}
    for job, scripts in gate_rows:
        if job not in wf_jobs:
            err(f"README CI gates: job '{job}' not in ci.yml "
                f"(workflow has: {sorted(wf_jobs)})")
        for s in scripts:
            if s not in wf_scripts:
                err(f"README CI gates: '{job}' claims `{s}` but the "
                    f"workflow never invokes it")
    for job in sorted(wf_jobs - table_jobs):
        err(f"ci.yml job '{job}' missing from the README CI gates table")
    # every gate script CI runs must be named somewhere in the docs
    doc_text = readme_text + design_text
    for s in sorted(wf_scripts):
        if s.startswith(("benchmarks/", "tools/")) and s not in doc_text:
            err(f"ci.yml invokes `{s}` but neither README.md nor "
                f"DESIGN.md mentions it")

# -- 5. README knobs table <-> RunConfig fields ------------------------------
# One row per dataclass field, backticked field name in the first cell.

import dataclasses


def parse_knob_table(readme: str) -> list[str]:
    """First-cell backticked names of the README "The knobs" table."""
    names: list[str] = []
    in_section = False
    for line in readme.splitlines():
        if re.match(r"^#{2,}\s+The knobs", line):
            in_section = True
            continue
        if in_section and line.startswith("#"):
            break
        if in_section and line.startswith("|"):
            cells = [c.strip() for c in line.strip("|").split("|")]
            if len(cells) < 2 or set(cells[0]) <= {"-", " ", ":"}:
                continue
            m = re.match(r"^`([A-Za-z_]+)`", cells[0])
            if m:
                names.append(m.group(1))
    return names


try:
    from repro.core.config import RunConfig as _RunConfig
except Exception as e:
    err(f"cannot import repro.core.config.RunConfig: {e}")
else:
    knob_rows = parse_knob_table(open(os.path.join(ROOT, "README.md")).read())
    cfg_fields = [f.name for f in dataclasses.fields(_RunConfig)]
    if not knob_rows:
        err('README.md: no "The knobs" table (## The knobs section)')
    for name in sorted(set(cfg_fields) - set(knob_rows)):
        err(f"README knobs table: RunConfig field `{name}` has no row")
    for name in sorted(set(knob_rows) - set(cfg_fields)):
        err(f"README knobs table: row `{name}` is not a RunConfig field")
    dupes = {n for n in knob_rows if knob_rows.count(n) > 1}
    for name in sorted(dupes):
        err(f"README knobs table: duplicate row `{name}`")

# -- 3. docstring audit ------------------------------------------------------

import importlib


def check_docstring(modname: str, attr):
    try:
        mod = importlib.import_module(modname)
    except Exception as e:  # jax etc. must be importable in CI
        err(f"cannot import {modname}: {e}")
        return
    if not (mod.__doc__ or "").strip():
        err(f"{modname}: module has no docstring")
    if attr is not None:
        obj = getattr(mod, attr, None)
        if obj is None:
            err(f"{modname}.{attr}: does not exist")
        elif not (getattr(obj, "__doc__", "") or "").strip():
            err(f"{modname}.{attr}: no docstring")


for modname, attr in PUBLIC_API:
    check_docstring(modname, attr)

# every repro module an example imports must have a module docstring
ex_dir = os.path.join(ROOT, "examples")
imported: set[str] = set()
for fn in sorted(os.listdir(ex_dir)):
    if not fn.endswith(".py"):
        continue
    tree = ast.parse(open(os.path.join(ex_dir, fn)).read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(
                a.name for a in node.names if a.name.startswith("repro")
            )
        elif isinstance(node, ast.ImportFrom) and node.module:
            if node.module.startswith("repro"):
                imported.add(node.module)
for modname in sorted(imported):
    check_docstring(modname, None)

if errors:
    print(f"\n{len(errors)} docs problem(s)")
    sys.exit(1)
print("docs OK: paths resolve, §-references valid, public API documented "
      f"({len(PUBLIC_API)} symbols + {len(imported)} example imports)")
