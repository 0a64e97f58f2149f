"""A run whose timed path is broken comes out not correct; a sound one
comes out correct. CPU, small widths, the chip check skipped."""

import pytest

from chipbench import tiny


def test_sound_training_run_is_correct():
    res = tiny.run(tiny.TRAIN)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_broken_training_step_is_caught(fault):
    res = tiny.run(tiny.TRAIN, fault=fault)
    assert not res["correct"], res["checks"]


def test_sound_serving_run_is_correct():
    res = tiny.run(tiny.SERVE, seconds=2.0)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0


def test_altered_token_is_caught():
    res = tiny.run(tiny.SERVE, seconds=2.0, fault="alter_token")
    assert not res["correct"], res["checks"]
