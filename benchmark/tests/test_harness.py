"""The harness finds every cell's configuration, traffic, reference and
per-layer metric readers by name, and a new cell needs only new files
and entries."""

import json
import os
import shutil

import pytest

from harness import core, gen
from harness.peaks import peaks

ROOT = os.path.dirname(core.BENCH)


def _spec():
    return core.load_spec(held=True)


@pytest.mark.parametrize("cell", [w["name"] for w in _spec()["workloads"]])
def test_every_cell_resolves(cell):
    c = core.Cell(cell, _spec())
    assert callable(c.generator())
    assert c.reference().rule_results
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(c.reader(m["name"]))
        assert m["moves"] in {e["name"] for e in c.end_to_end}
    assert os.path.isdir(os.path.join(ROOT, c.config["policies"]))


def test_config_files_are_listed():
    spec = _spec()
    for cfg in spec["configs"]:
        with open(os.path.join(ROOT, cfg["file"])) as f:
            doc = json.load(f)
        assert doc["name"] == cfg["name"] and doc["reduced"] == cfg["reduced"]
        assert doc["source"] == cfg["source"]


def test_a_new_cell_needs_only_files_and_entries(tmp_path):
    bench = tmp_path / "benchmark"
    shutil.copytree(core.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    traffic = json.loads((bench / "traffic" / "scan-rollout.json").read_text())
    traffic["wave_pods"] = 250
    (bench / "traffic" / "scan-small-waves.json").write_text(json.dumps(traffic))
    (bench / "metrics" / "waves.scan.py").write_text(
        "def read(run):\n    return run.spans.get('waves')\n")
    spec = _spec()
    spec["workloads"].append({"name": "pss-restricted.scan-small-waves",
                              "config": "pss-restricted",
                              "traffic": "scan-small-waves", "chips": 1,
                              "why": "smaller waves"})
    spec["per_layer"].append({"name": "waves.scan", "unit": "waves",
                              "better": "higher", "source": "host_clock",
                              "layer": "background scan",
                              "moves": "rollout_pods_per_s",
                              "workloads": ["pss-restricted.scan-small-waves"]})
    for m in spec["end_to_end"]:
        if "pss-restricted.scan-rollout" in m.get("workloads", []):
            m["workloads"].append("pss-restricted.scan-small-waves")
    cell = core.Cell("pss-restricted.scan-small-waves", spec, str(bench))
    assert cell.traffic["wave_pods"] == 250
    assert [m["name"] for m in cell.per_layer] == ["waves.scan"]
    run = core.Run(cell, 1, 1.0, True)
    run.spans["waves"] = 7
    try:
        assert core.result_line(run)["metrics"] == {
            "waves.scan": {"value": 7, "unit": "waves"}}
    finally:
        run.cleanup()


def test_a_metric_with_nothing_to_read_is_left_out():
    run = core.Run(core.Cell("pss-restricted.scan-rollout", _spec()), 1, 1.0, True)
    try:
        line = core.result_line(run)
    finally:
        run.cleanup()
    assert line["metrics"] == {}
    assert "breakdown" not in line and list(line)[-1] == "checks"


def test_correct_is_the_conjunction_of_the_printed_checks(capsys):
    checks = core.Checks()
    checks.add("a", 0, 0)
    assert checks.correct()
    checks.add("b", 1, 0)
    assert not checks.correct()
    checks.print_last()
    err = capsys.readouterr().err.splitlines()
    assert err[-3:] == ["check a: 0 (limit 0)", "check b: 1 (limit 0)",
                        "correct: False"]


def test_held_cells_are_whole_and_out_of_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        shipped = {w["name"] for w in json.load(f)["workloads"]}
    held = {w["name"] for w in _spec()["workloads"]} - shipped
    assert held == {"pss-restricted.scan-rollout"}
    names = [m["name"] for k in ("end_to_end", "per_layer") for m in _spec()[k]]
    assert len(names) == len(set(names))
    with pytest.raises(KeyError):
        core.Cell("pss-restricted.scan-rollout")


def test_gc_share_reads_full_collections_inside_the_window():
    run = core.Run(core.Cell("pss-restricted.scan-rollout", _spec()), 1, 1.0, True)
    run.cleanup()
    read = run.cell.reader("gc_full_share.scan")
    assert read(run) is None
    run.spans["window_wall"] = (100.0, 110.0)
    run.gc_full = [[99.5, 1.0, 5], [105.0, 0.5, 7], [109.8, 0.4, 3]]
    assert read(run) == pytest.approx(100.0 * (0.5 + 0.5 + 0.2) / 10.0)


def test_peaks_table():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")


def test_a_new_arrival_shape_needs_only_a_traffic_file(tmp_path):
    """An on/off burst of Pod CREATEs is a data file and an entry: the
    general admission generator reads its rate phases."""
    bench = tmp_path / "benchmark"
    shutil.copytree(core.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    traffic = json.loads((bench / "traffic" / "admission-steady.json").read_text())
    traffic.update(rate_per_s=25, phases=[[0.5, 4.0], [1.5, 0.0]])
    (bench / "traffic" / "admission-burst.json").write_text(json.dumps(traffic))
    spec = _spec()
    name = "pss-restricted-enforce.admission-burst"
    spec["workloads"].append({"name": name, "config": "pss-restricted-enforce",
                              "traffic": "admission-burst", "chips": 1,
                              "why": "on/off bursts"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "pss-restricted-enforce.admission-steady" in m.get("workloads", []):
            m["workloads"].append(name)
    cell = core.Cell(name, spec, str(bench))
    assert cell.generator().__module__ == "harness.open_loop_admission"
    cluster = dict(cell.config["cluster"], pods=2000)
    offsets = gen.AdmissionStream(5, cluster, cell.traffic, "window").schedule(
        cell.traffic["rate_per_s"], 10.0)
    assert len(offsets) == 250
    assert all(t % 2.0 <= 0.5 + 1e-9 for t in offsets)
    assert {m["name"] for m in cell.end_to_end} == {
        "admission_p50_ms", "admission_p95_ms", "setup_s"}
