"""The controls at a size a test run holds: the reference put in the
program's place, one precision below what the configuration states or
with a fault planted, judged by the cell's own limits
(``run_cell(..., controls=...)``)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402


def test_fp8_step_and_half_batch_fail_the_training_limits():
    # the reference as an fp8 training step (operands e4m3, cotangents
    # e5m2, a scale a tensor) fails the gradient next to the loss; with
    # half the batch left out it fails the norms as well
    result, compared = run.run_cell("tiny_train", 21, 1.0, rehearsal=True,
                                    controls=("fp8", "half_batch"))
    assert result["correct"], compared
    assert "head_grad_diff" in result["controls"]["fp8"]
    assert {"head_grad_diff", "grad_norm_gap.median",
            "update_norm_gap.median"} <= set(result["controls"]["half_batch"])


def test_int8_control_fails_the_serving_limit():
    # the widest gap swings by its nature and a toy run compares few
    # tokens: the control has to fail on one of three seeds at least,
    # the program on none
    failed = []
    for seed in (1, 2, 3):
        result, compared = run.run_cell("tiny_backlog", seed, 2.0,
                                        rehearsal=True, controls=("int8",))
        assert result["correct"], compared
        failed += result["controls"]["int8"]
    assert failed
