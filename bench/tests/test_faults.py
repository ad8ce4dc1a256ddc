"""A run whose timed path is broken underneath reports correct false:
one case for each fault a cell can have. The harness's look for a chip
is skipped and the rest of the run is driven at a CPU size."""
import jax
import pytest

from bench.tests.checkout import make_checkout, run_cell


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("checkout"))


def _state_unchanged(monkeypatch):
    """Every fit block update returns the sketch state it was given."""
    import repro.stream.accumulate as acc
    monkeypatch.setattr(acc, "_fused_block_update",
                        lambda X, W, rn, *a, **k: (W, rn))


def _half_block(monkeypatch):
    """Every fit block update folds in half of the block's columns."""
    import repro.stream.accumulate as acc
    orig = acc._fused_block_update

    def half(X, W, rn, aux, rows, q, *, b, **kw):
        return orig(X, W, rn, aux, rows, q, b=max(b // 2, 1), **kw)
    monkeypatch.setattr(acc, "_fused_block_update", half)


def _fit_label_altered(monkeypatch):
    """The fit's K-means hands back one training label changed."""
    import repro.api.estimator as est
    orig = est.kmeans

    def altered(*a, **k):
        res = orig(*a, **k)
        k_ = int(res.centroids.shape[0])
        return res._replace(labels=res.labels.at[0].set(
            (res.labels[0] + 1) % k_))
    monkeypatch.setattr(est, "kmeans", altered)


def _served_label_altered(monkeypatch):
    """The serving assignment hands back one label changed per batch."""
    import repro.serve.extend as ext
    orig = ext.assign_pallas

    def altered(Y, C, *a, **k):
        labels, d2 = orig(Y, C, *a, **k)
        return labels.at[0].set((labels[0] + 1) % C.shape[0]), d2
    monkeypatch.setattr(ext, "assign_pallas", altered)


@pytest.mark.parametrize("cell,fault", [
    ("tiny-fit", _state_unchanged),
    ("tiny-fit", _half_block),
    ("tiny-fit", _fit_label_altered),
    ("tiny-serve", _served_label_altered),
])
def test_fault_makes_the_run_incorrect(root, monkeypatch, cell, fault):
    jax.clear_caches()
    fault(monkeypatch)
    result = run_cell(root, cell, seed=2**33 + 3, seconds=1.0)
    assert result["correct"] is False, result["checks"]
    failed = [k for k, v in result["checks"].items()
              if v["value"] > v["limit"]]
    assert failed, result["checks"]


def test_the_same_runs_pass_unbroken(root):
    for cell in ("tiny-fit", "tiny-serve"):
        result = run_cell(root, cell, seed=2**33 + 3, seconds=1.0)
        assert result["correct"] is True, (cell, result["checks"])
