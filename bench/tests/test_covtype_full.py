"""covtype-full-fit's path at a size the CPU runs: a fixture
configuration with the settings of covtype-full-rbf-onepass (p = 54,
k = 7, r = 7, oversampling 10, block 512) and the residues of its
n = 581,012, a ragged tail of 404 columns and an SRHT padded past n
(n = 4,500 = 8 x 512 + 404, n_pad = 8,192). The program agrees with the
reference there, and a broken block update is caught."""
import json
import pathlib

import jax
import pytest

from bench.tests.checkout import make_checkout, run_cell
from bench.tests.test_faults import _half_block

CONFIG = "covtype-residues-rbf-onepass"
CELL = "covtype-residues-fit"
FIT_METRICS = ("fit_cols_per_s", "fit_sketch_roofline_pct",
               "fit_nonkernel_ms", "publish_ms", "device_idle_pct.fit")


def residues_checkout(dest: pathlib.Path) -> pathlib.Path:
    """make_checkout, then the fixture's configuration and cell as new
    BENCHMARK.json entries, reported where covtype-full-fit is."""
    root = make_checkout(dest)
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["configs"].append({
        "name": CONFIG, "source": "test fixture",
        "file": f"bench/configs/{CONFIG}.json", "reduced": ["n"],
        "why": "test fixture"})
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "fit_jobs", "chips": 1,
        "why": "test fixture: covtype-full-fit's residues at a CPU size"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in FIT_METRICS:
            assert "covtype-full-fit" in m["workloads"], m["name"]
            m["workloads"].append(CELL)
    path.write_text(json.dumps(bench, indent=1))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return residues_checkout(tmp_path_factory.mktemp("checkout"))


def test_the_fixture_has_covtype_fulls_residues(root):
    from bench.lib import spec
    full = spec.resolve("covtype-full-fit")
    small = spec.resolve(CELL, root)
    keep = ("p", "k", "r", "oversampling", "block", "kernel", "backend",
            "matmul_precision", "kernels", "reference")
    assert {k: small.config[k] for k in keep} == \
        {k: full.config[k] for k in keep}
    n, n_full, b = small.config["n"], full.config["n"], full.config["block"]
    assert n % b == n_full % b == 404
    assert 1 << (n - 1).bit_length() > n
    assert [m["name"] for m in small.end_to_end] == \
        [m["name"] for m in full.end_to_end]
    assert sorted(small.readers) == sorted(full.readers)


def test_the_residues_run_agrees_with_the_reference(root):
    result = run_cell(root, CELL, seed=2**33 + 15, seconds=1.0)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"fit_cols_per_s", "setup_s"}


def test_a_half_block_update_makes_the_residues_run_incorrect(
        root, monkeypatch):
    jax.clear_caches()
    _half_block(monkeypatch)
    result = run_cell(root, CELL, seed=2**33 + 15, seconds=1.0)
    assert result["correct"] is False, result["checks"]
    failed = [k for k, v in result["checks"].items()
              if v["value"] > v["limit"]]
    assert failed, result["checks"]
