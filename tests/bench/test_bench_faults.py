"""``correct`` comes out false for the control and for the faults the
committed limits of ``mnist_cnn.grid_k16`` catch, at a size a test run
holds: the chip's check skipped, the rest of a run driven with the timed
path broken underneath."""
import pytest
import tiny_bench


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_bench.make_root(tmp_path_factory.mktemp("bench"))


def test_control_is_not_correct(root):
    """The reference in bfloat16 in the program's place."""
    from bench import calibrate, check, harness

    cell = harness.load_cell(root, tiny_bench.WORKLOAD)
    run, ref, _ = calibrate.program_numbers(harness, check, cell, tiny_bench.SEED)
    got = calibrate.control_numbers(harness, check, run, ref)
    assert any(got[k] > limit for k, limit in cell.limits.items()), got


@pytest.mark.parametrize("fault", ["state_unchanged", "state_answer",
                                   "half_batch"])
def test_fault_is_not_correct(root, fault):
    from bench import calibrate

    with calibrate.FAULTS[fault]():
        result = tiny_bench.run(root)
    assert not result["correct"], result["checks"]
    assert result["failed"] >= 1
