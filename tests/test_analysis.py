"""The static-analysis pass (repro.analysis): corpus + clean-repo gate.

Two-sided validation of the linter itself (DESIGN.md §10.5): every
seeded-violation corpus case must be flagged with its expected rule id
(the analyzer finds what it claims to find), and the repo must lint
clean (the rules describe the code as it actually is).
"""
from __future__ import annotations

import ast
import json
from pathlib import Path

import pytest

from repro.analysis import astutil, corpus, linter
from repro.analysis.bench_schema import classify_summary_key
from repro.analysis.report import RULES, Finding, Report

ROOT = Path(__file__).resolve().parents[1]
CORPUS_DIR = ROOT / "tests" / "analysis_corpus"
MANIFEST = json.loads((CORPUS_DIR / "manifest.json").read_text())


@pytest.mark.parametrize("case", sorted(MANIFEST))
def test_corpus_case_flagged(case):
    result = corpus.run_case(CORPUS_DIR / case, MANIFEST[case])
    assert result.ok, str(result)


def test_corpus_rules_are_known():
    for case, spec in MANIFEST.items():
        for rule in spec["rules"]:
            assert rule in RULES, (case, rule)


def test_corpus_covers_every_family():
    seeded = {r for spec in MANIFEST.values() for r in spec["rules"]}
    assert {"KC01", "KC02", "KC03", "KC04", "KC05", "KC06", "KC07",
            "KC08", "OR01", "OR03", "EN01", "EN02", "EN03"} <= seeded
    assert len(MANIFEST) >= 10


def test_repo_lints_clean():
    report = linter.lint_repo(ROOT)
    assert report.ok, "\n".join(str(f) for f in report.findings)
    # the contract registry is populated and every registered kernel
    # module contributed at least one contract
    modules = {m for m, _ in linter.REGISTRY}
    assert modules == set(linter.KERNEL_MODULES)


def test_report_json_shape():
    f = Finding(rule="KC01", path="a.py", line=3, message="m")
    report = Report(findings=[f])
    doc = json.loads(report.to_json())
    assert doc["ok"] is False
    assert doc["counts"] == {"KC01": 1}
    assert doc["findings"][0]["description"] == RULES["KC01"]
    assert str(f) == "a.py:3: KC01 m"
    assert Report().ok


def test_bench_key_classifier():
    assert classify_summary_key("speedup_vs_ref") == "gated-ratio"
    assert classify_summary_key("pallas_compiled") == "gated-bound"
    assert classify_summary_key("p50_ms") == "parity"
    assert classify_summary_key("qps_mean") == "parity"
    assert classify_summary_key("shards") == "parity"
    assert classify_summary_key("frobnication_index") == "unknown"


def test_all_bench_keys_classify():
    # the repo's own BENCH files obey the convention end to end
    for name in ("BENCH_updates.json",):
        path = ROOT / name
        data = json.loads(path.read_text())
        for run in data.get("runs", []):
            for key in run.get("summary", {}):
                assert classify_summary_key(key) != "unknown", (name, key)


def test_cdiv_normalization_equates_spellings():
    a = ast.parse("def f(d, bd):\n    nt = pl.cdiv(d, bd)\n    return nt\n")
    b = ast.parse("def f(d, bd):\n    nt = -(-d // bd)\n    return nt\n")
    c = ast.parse("def f(d, bd):\n    nt = d // bd\n    return nt\n")
    dump = astutil.normalized_body_dump
    fa, fb, fc = (t.body[0] for t in (a, b, c))
    assert dump(fa) == dump(fb)
    assert dump(fa) != dump(fc)


def test_pallas_site_extraction_on_real_kernel():
    sf = astutil.load(ROOT / "src" / "repro" / "kernels" / "knn_topk.py")
    sites = {s.entry: s for s in astutil.find_pallas_sites(sf.tree)}
    assert set(sites) == {"knn_topk", "knn_topk_dtiled"}
    mono = sites["knn_topk"]
    assert len(mono.grid) == 2 and mono.grid_parsed
    assert mono.kernel_body == "_kernel"
    assert [s.arity for s in mono.in_specs] == [2, 2, 2]
    assert mono.scratch_dtypes == ["float32", "float32", "int32",
                                   "float32", "int32"]
    dt = sites["knn_topk_dtiled"]
    assert len(dt.grid) == 3
    assert dt.scratch_dtypes == ["float32", "float32", "float32", "int32",
                                 "float32", "int32"]
