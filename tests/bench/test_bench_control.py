"""The control comes out not correct, while the same runs without it are
correct. On the CPU the program's default-precision path changes no bit,
so the test runs the control's CPU form: the constraint contractions with
the capacity matrix in bfloat16, as the default precision rounds it on the
chip."""
import pytest

# At this size a run commits a few dozen allocations, and the control
# leaves one short on most seeds, not all (serve seed 6 covers every
# demand); on the chip the control fails at the cells' size (PERF.md).
RUNS = [("replay-tiny-mixed", 5, 2.0), ("replay-tiny-mixed", 6, 2.0),
        ("serve-tiny-scan", 7, 8.0), ("serve-tiny-scan", 8, 8.0)]


@pytest.mark.parametrize("cell,seed,seconds", RUNS)
def test_control_is_not_correct(cell, seed, seconds, run_tiny):
    from bench.control import bf16_capacities

    with bf16_capacities():
        control = run_tiny(cell, seed, seconds)
    assert control["correct"] is False, control["checks"]
    assert control["checks"]["shortfall_raw"]["value"] > 1e-3
    sound = run_tiny(cell, seed, seconds)
    assert sound["correct"] is True, sound["checks"]


def test_default_precision_switches_the_programs_path_and_restores_it():
    import jax

    import repro.core.objective as obj
    import repro.fleet.solver as fleet
    from bench.control import default_precision

    highest = jax.lax.Precision.HIGHEST
    assert obj.CONSTRAINT_PRECISION == fleet.CONSTRAINT_PRECISION == highest
    with default_precision():
        assert obj.CONSTRAINT_PRECISION == jax.lax.Precision.DEFAULT
        assert fleet.CONSTRAINT_PRECISION == jax.lax.Precision.DEFAULT
    assert obj.CONSTRAINT_PRECISION == fleet.CONSTRAINT_PRECISION == highest
