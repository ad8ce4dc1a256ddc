"""A new configuration, traffic mix and per-layer metric join the
benchmark as new files and new BENCHMARK.json entries, with no existing
file edited; the harness finds them by name."""
import filecmp
import pathlib

from bench.lib import spec
from bench.tests.checkout import REPO, make_checkout, run_cell


def test_fixture_files_are_found_by_name(tmp_path):
    root = make_checkout(tmp_path)
    cell = spec.resolve("tiny-fit", root)
    assert cell.config["name"] == "tiny-rbf-onepass"
    assert cell.traffic["driver"] == "fit_jobs"
    assert "fixture_jobs_completed" in cell.readers
    serve = spec.resolve("tiny-serve", root)
    assert serve.traffic["max_width"] == 64
    assert [m["name"] for m in serve.end_to_end] == ["assign_p50_ms",
                                                      "setup_s"]
    # every file the benchmark already had is byte for byte unchanged
    for f in (REPO / "bench").rglob("*"):
        rel = f.relative_to(REPO)
        if f.is_file() and "tests" not in rel.parts \
                and "__pycache__" not in rel.parts:
            assert filecmp.cmp(f, root / rel, shallow=False), rel


def test_every_committed_cell_resolves():
    bench = spec.load_benchmark()
    for wl in bench["workloads"]:
        cell = spec.resolve(wl["name"])
        assert cell.end_to_end and cell.per_layer, wl["name"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)


def test_fixture_metric_reaches_the_result_line(tmp_path):
    root = make_checkout(tmp_path)
    result = run_cell(root, "tiny-fit", seed=2**33 + 7, seconds=1.0,
                      trace=1)
    assert result["correct"], result["checks"]
    assert result["metrics"]["fixture_jobs_completed"]["value"] >= 1
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"


def test_end_to_end_line_of_a_serving_fixture(tmp_path):
    root = make_checkout(tmp_path)
    result = run_cell(root, "tiny-serve", seed=11, seconds=1.0, trace=0)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"assign_p50_ms", "setup_s"}
    assert result["device"]["platform"] == "cpu"
    assert pathlib.Path(root / "BENCHMARK.json").is_file()


def test_fixed_width_traffic_compiles_nothing_in_the_window(tmp_path,
                                                            capsys):
    root = make_checkout(tmp_path)
    result = run_cell(root, "tiny-serve-fixed", seed=2**33 + 2,
                      seconds=2.0, trace=0)
    assert result["correct"], result["checks"]
    err = capsys.readouterr().err
    assert "bench: window compiled 0 programs" in err, err[-2000:]
