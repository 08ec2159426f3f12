"""Docs tree integrity: intra-repo markdown links resolve, docstring
examples run (doctest), and docs/observations.md stays in sync with the
observation registry.  CI's ``docs`` job runs exactly this module.
"""
import doctest
import importlib
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

MD_FILES = sorted(p for p in REPO.glob("**/*.md")
                  if not any(part.startswith(".") or part in
                             ("node_modules", "results", "related")
                             for part in p.relative_to(REPO).parts))

#: Public modules whose docstring examples must be runnable.
DOCTEST_MODULES = (
    "repro.core.arrival",
    "repro.core.chain_program",
    "repro.core.spans",
    "repro.core.device",
    "repro.core.workload",
    "repro.core.latency",
    "repro.core.metrics",
    "repro.experiments",
    "repro.experiments.registry",
    "repro.experiments.runner",
    "repro.host.scenarios",
    "repro.cluster.codec",
    "repro.cluster.placement",
    "repro.cluster.cluster",
)

_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")


def test_markdown_corpus_nonempty():
    names = {p.name for p in MD_FILES}
    assert {"README.md", "architecture.md", "observations.md",
            "api.md"} <= names


@pytest.mark.parametrize("md", MD_FILES, ids=lambda p: str(p.relative_to(REPO)))
def test_intra_repo_markdown_links_resolve(md):
    broken = []
    for target in _LINK.findall(md.read_text(encoding="utf-8")):
        if target.startswith(("http://", "https://", "mailto:", "#")):
            continue
        path = target.split("#", 1)[0]
        if not path:
            continue
        if not (md.parent / path).resolve().exists():
            broken.append(target)
    assert not broken, f"{md}: broken relative links {broken}"


@pytest.mark.parametrize("module", DOCTEST_MODULES)
def test_docstring_examples_run(module):
    mod = importlib.import_module(module)
    res = doctest.testmod(mod, verbose=False,
                          optionflags=doctest.NORMALIZE_WHITESPACE)
    assert res.attempted > 0, f"{module}: no doctest examples found"
    assert res.failed == 0, f"{module}: {res.failed} doctest failures"


def test_exactness_matrix_doc_in_sync_with_benchmark():
    """docs/architecture.md's exactness-matrix table must cover every
    axis and tolerance the CI gate (benchmarks/exactness_matrix.py)
    actually enforces."""
    import sys
    sys.path.insert(0, str(REPO))
    from benchmarks.exactness_matrix import (
        LAYOUTS, TOL_JITTER_FREE, TOL_JITTERED, WORKLOADS)
    text = (REPO / "docs" / "architecture.md").read_text(encoding="utf-8")
    for token in (*WORKLOADS, *LAYOUTS):
        assert f"`{token}`" in text, \
            f"docs/architecture.md exactness matrix is missing `{token}`"
    for tol in (TOL_JITTER_FREE, TOL_JITTERED):
        tok = f"{tol:.0e}".replace("e-0", "e-")
        assert f"`{tok}`" in text, \
            f"docs/architecture.md is missing gate tolerance `{tok}`"


def test_observations_doc_in_sync_with_registry():
    from repro.experiments import all_experiments
    text = (REPO / "docs" / "observations.md").read_text(encoding="utf-8")
    for exp in all_experiments():
        assert exp.name in text, \
            f"docs/observations.md is missing registry entry {exp.name}"
        for knob in exp.knobs:
            assert knob in text, \
                f"docs/observations.md is missing {exp.name} knob {knob}"
        for t in exp.tests:
            assert t.split("::")[-1] in text, \
                f"docs/observations.md is missing {exp.name} test {t}"
    assert f"| #{len(all_experiments())} |" in text


def test_readme_quickstart_mentions_experiments_cli():
    text = (REPO / "README.md").read_text(encoding="utf-8")
    assert "python -m repro.experiments run --all" in text
    assert "docs/observations.md" in text


def test_solver_docs_in_sync_with_solve_stats_and_knobs():
    """docs/api.md must document every SolveStats field and the PR 10
    solver knobs exactly as the code exposes them; architecture.md must
    carry the matching solver-section narrative."""
    import dataclasses
    import inspect

    from repro.core import SolveStats, solve_program_windowed
    from repro.cluster.capacity import plan_capacity

    api = (REPO / "docs" / "api.md").read_text(encoding="utf-8")
    arch = (REPO / "docs" / "architecture.md").read_text(encoding="utf-8")

    for f in dataclasses.fields(SolveStats):
        assert f.name in api, f"docs/api.md is missing SolveStats.{f.name}"
    for token in ("last_solve_stats", "solve_stats",
                  'fixpoint="windowed"', "solve_program_windowed",
                  "window_program", "n_windows", "window_events",
                  "warm_ladder=True", "--warm-ladder", "warm_hits",
                  "unjustified_slots"):
        assert token in api, f"docs/api.md is missing {token}"
    # the documented knobs exist with those exact names
    sig = inspect.signature(solve_program_windowed)
    assert {"n_windows", "window_events"} <= set(sig.parameters)
    assert "warm_ladder" in inspect.signature(plan_capacity).parameters

    for token in ("Active-set sweeps", "window_program",
                  "solve_program_windowed", "warm_ladder=True",
                  "SolveStats", "unjustified_slots", "warm_hits"):
        assert token in arch, f"docs/architecture.md is missing {token}"
