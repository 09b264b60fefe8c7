"""The step-summary writer of the CI benchmark gate
(scripts/bench_regression.py) as a unit."""
from __future__ import annotations

import importlib.util
import os

import pytest

_SCRIPTS = os.path.join(os.path.dirname(__file__), os.pardir, "scripts")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_SCRIPTS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench_regression():
    return _load("bench_regression")


class TestStepSummary:
    def test_writes_markdown_table(self, bench_regression, tmp_path,
                                   monkeypatch):
        out = tmp_path / "summary.md"
        monkeypatch.setenv("GITHUB_STEP_SUMMARY", str(out))
        rows = [("pallas.1/wall_s", 100.0, 90.0, 0.9, False),
                ("dense.1/wall_s", 100.0, 70.0, 0.7, True)]
        bench_regression.write_step_summary(
            [("BENCH_e2e.json", rows, ["gated.1/wall_s"], None),
             ("BENCH_eval.json", [], [], "skipped: config mismatch")], 0.2)
        text = out.read_text()
        assert "| `pallas.1/wall_s` | 100 | 90 | -10.0% |" in text
        assert "regressed" in text  # the -30% row is flagged
        assert "`gated.1/wall_s`" in text and "skipped" in text
        assert "config mismatch" in text

    def test_noop_outside_actions(self, bench_regression, monkeypatch):
        monkeypatch.delenv("GITHUB_STEP_SUMMARY", raising=False)
        bench_regression.write_step_summary(
            [("BENCH_e2e.json", [], [], "note")], 0.2)  # must not raise
