"""The benchmark's own byte models agree with the kernels' declared
memory contracts at every registered parity shape, and the work a fit
needs matches the arithmetic from its shape."""
import pytest

from bench.lib import costs, peaks


def _cases(name):
    import repro.kernels  # noqa: F401  (registers every kernel package)
    from repro.kernels.registry import get_contract, get_kernel
    return get_kernel(name).cases, get_contract(name).declared


@pytest.mark.parametrize("kernel,moved,keys", [
    ("fit_sketch", costs.fit_sketch_moved_bytes, ("p", "m", "b", "rp")),
    ("extend_embed", costs.extend_embed_moved_bytes, ("p", "n", "r", "w")),
    ("kmeans_assign", costs.kmeans_assign_moved_bytes, ("n", "r", "k")),
])
def test_moved_bytes_match_memory_contract(kernel, moved, keys):
    cases, declared = _cases(kernel)
    for case in cases:
        want = declared(case)["hbm_bytes"]
        assert moved(*(case[k] for k in keys)) == want, case


def test_needed_work_is_at_most_what_a_call_moves():
    p, rp, b = 784, 20, 512
    for q in (0, 512, 35_000, 69_120):
        flops, hbm = costs.fit_sketch_block_needed(p, q, b, rp)
        assert 0 < hbm <= costs.fit_sketch_moved_bytes(p, 70_000, b, rp)
        assert flops > 0


def test_a_whole_fit_needs_about_n_squared_over_two_entries():
    n, p, rp, block = 70_000, 784, 20, 512
    flops, hbm, calls = costs.fit_sketch_fit_needed(n, p, rp, block)
    assert calls == 137
    per_entry = 2 * p + costs.RBF_ENTRY_OPS + 4 * rp + 3
    # sum over blocks of (q + b) * b entries = n (n + b) / 2 for full blocks
    assert flops == pytest.approx(n * (n + block) / 2 * per_entry, rel=0.01)
    assert flops == pytest.approx(4.1e12, rel=0.05)


def test_roofline_picks_the_binding_resource():
    bound = peaks.roofline_seconds(197e12, 819e9 / 2, "TPU v5 lite")
    assert bound["seconds"] == pytest.approx(1.0)
    assert bound["bound"] == "compute"
    assert peaks.roofline_seconds(1.0, 819e9, "TPU v5 lite",
                                  chips=4)["seconds"] == pytest.approx(0.25)
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
