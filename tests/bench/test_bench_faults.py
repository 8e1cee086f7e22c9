"""The correctness check sees a broken timed path: each fault a cell can
have (``bench/faults.py``), planted under a tiny run with the device check
skipped, makes ``correct`` come out false."""
import pytest

from bench.faults import WARM_STEP_FAULTS


@pytest.mark.parametrize("fault", sorted(WARM_STEP_FAULTS))
def test_serve_fault_is_not_correct(fault, run_tiny):
    from bench.faults import planted

    with planted(fault, "serve"):
        res = run_tiny("serve-tiny-scan", seed=2, seconds=4.0)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["shortfall_raw"]["value"] > 1e-6


@pytest.mark.parametrize("fault", sorted(WARM_STEP_FAULTS))
def test_replay_fault_is_not_correct(fault, run_tiny):
    from bench.faults import planted

    # seed 5: the odd lane's demand rises from the first tick to the
    # second, so even a run slow enough to replay one segment shows a lane
    # left out (with falling demand, a lane that keeps its allocation still
    # answers correctly)
    with planted(fault, "replay"):
        res = run_tiny("replay-tiny-mixed", seed=5, seconds=2.0)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["shortfall_raw"]["value"] > 1e-6


def test_scale_down_skipped_is_not_correct(run_tiny):
    from bench.faults import planted

    with planted("scale_down_skipped", "replay"):
        res = run_tiny("replay-tiny-mixed", seed=2, seconds=2.0)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["removable_share"]["value"] > 0.01


def test_sound_tiny_runs_are_correct(run_tiny):
    for cell, seconds in (("serve-tiny-scan", 4.0), ("replay-tiny-mixed",
                                                     2.0)):
        res = run_tiny(cell, seed=2, seconds=seconds)
        assert res["correct"] is True, (cell, res["checks"])


@pytest.mark.parametrize("fault", ["unchanged", "scale_down_skipped"])
def test_planted_fault_is_taken_out_after_the_block(fault):
    import repro.core.rounding as rounding
    import repro.serve.engine as engine
    from bench.faults import planted

    before = engine.solve_fleet_step, rounding.scale_down
    with planted(fault, "serve"):
        assert (engine.solve_fleet_step, rounding.scale_down) != before
    assert (engine.solve_fleet_step, rounding.scale_down) == before
