"""The control: the plain reference at the precision below the
configuration's (float32 in three bfloat16 passes), put in the program's
place, comes out as not correct, while the program passes; at a size
the CPU holds, under the fixture configuration's limits, which sit
between the two readings at this size (the chip readings at the cells'
own sizes are in PERF.md)."""
import jax
import pytest

from bench import control
from bench.lib import device, spec
from bench.tests.checkout import make_checkout

SEEDS = [3, 2**33 + 11, 2**31 + 5]


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    root = make_checkout(tmp_path_factory.mktemp("checkout"))
    cell = spec.resolve("tiny-fit", root)
    device.configure(cell.config["matmul_precision"])
    return list(control.readings(cell, SEEDS, SEEDS, 0.5,
                                 jax.devices()[:1], interpret=True))


def _ok(values):
    return all(v <= lim for v, lim in values.values())


def test_program_passes_and_control_fails_on_every_seed(readings):
    by = {(seed, kind): values for seed, kind, values in readings}
    for seed in SEEDS:
        assert _ok(by[seed, "program"]), by[seed, "program"]
        assert not _ok(by[seed, "control"]), by[seed, "control"]


def test_control_reads_three_times_the_program_or_more(readings):
    prog = max(v["sketch_rel_err"][0] for _, k, v in readings
               if k == "program")
    ctrl = min(v["sketch_rel_err"][0] for _, k, v in readings
               if k == "control")
    assert ctrl >= 3 * prog, (prog, ctrl)
