"""``BENCHMARK.json`` is whole: every cell finds its files, every metric its
reader, and the cells keep to the counts the benchmark allows."""
import json
import numbers

import pytest

import sizes

from chipbench import run

BENCH = run.load_bench()
CELLS = {c["name"]: c for c in BENCH["workloads"]}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_exist_and_load(cell):
    c = CELLS[cell]
    config = json.loads((sizes.ROOT / run._config_entry(BENCH, c)["file"])
                        .read_text())
    traffic = json.loads((run.BENCH / "traffic" / f"{c['traffic']}.json")
                         .read_text())
    limits = json.loads((run.BENCH / "limits" / f"{cell}.json").read_text())
    assert config and limits
    assert (run.BENCH / "drivers" / f"{traffic['driver']}.py").is_file()
    if "rate_per_s" in traffic:
        rate = traffic["rate_per_s"]
        assert isinstance(rate, numbers.Real) and rate > 0, rate


@pytest.mark.parametrize("name", sorted(m["name"] for m in METRICS))
def test_metric_has_a_reader_that_agrees(name):
    m = next(m for m in METRICS if m["name"] == name)
    assert callable(run.reader(m).read)
    assert set(m.get("workloads", CELLS)) <= set(CELLS), m["workloads"]


def test_cell_counts():
    cells = BENCH["workloads"]
    four = sum(c["chips"] == 4 for c in cells)
    assert len(cells) <= 24
    assert four <= max(1, len(cells) // 2)


def test_every_cell_reports_what_it_must():
    for c in BENCH["workloads"]:
        e2e = {m["name"] for m in run.metrics_for(BENCH, c, False)}
        assert "setup_s" in e2e and len(e2e) >= 2, (c["name"], e2e)
        assert run.metrics_for(BENCH, c, True), c["name"]
