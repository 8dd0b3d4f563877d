"""The comparison that decides ``correct`` in the fused cell sees each fault
the cell can have, and the control: the harness's load generator and
comparison on the CPU at a tiny width, with the timed path broken
underneath."""
import jax
import jax.numpy as jnp
import pytest

from bench import calibrate, compare, drive, run as bench_run
from bench_fault_helpers import (SEED, altered_accuracy, altered_pick,
                                 cell, correct_after, unchanged_state,
                                 wrapped)


@pytest.fixture
def fused():
    return cell("vgg11-paper.fused", check_calls=1)


def test_fused_sound_run_is_correct(fused):
    assert correct_after(*fused)


def test_fused_state_left_unchanged_fails(fused):
    with wrapped("fused_rounds", unchanged_state):
        assert not correct_after(*fused)


def test_fused_answer_altered_fails(fused):
    with wrapped("fused_rounds", altered_pick):
        assert not correct_after(*fused)


def test_fused_eval_answer_altered_fails(fused):
    with wrapped("fused_rounds", altered_accuracy):
        assert not correct_after(*fused)


def test_fused_half_batch_fails(fused, monkeypatch):
    from repro.models import vgg
    orig = vgg.masked_xent_loss

    def half(logits, labels, mask):
        # the rows past the first half of the valid ones leave the mean
        keep = jnp.cumsum(mask, axis=-1) <= (jnp.sum(mask) + 1) // 2
        return orig(logits, labels, mask * keep)

    monkeypatch.setattr(vgg, "masked_xent_loss", half)
    jax.clear_caches()
    try:
        assert not correct_after(*fused)
    finally:
        jax.clear_caches()


def test_fused_half_batch_in_train_gather_fails(fused):
    with calibrate.half_batch_gather():
        assert not correct_after(*fused)


def test_fused_control_fails(fused):
    jax.clear_caches()
    try:
        with calibrate.x64_off(jax):
            assert not correct_after(
                *fused, scenario_override={"dtype": "bf16"})
    finally:
        jax.clear_caches()


def test_fused_bf16_reference_in_place_fails(fused):
    config, traffic, limits = fused
    d = drive.make(config, traffic, SEED)
    d.setup()
    d.free()
    kept = calibrate.reference_in_place(config, traffic, SEED, d.kept,
                                        jnp.bfloat16)
    nums = compare.fused_numbers(config, traffic, SEED, kept,
                                 parts=("train",))
    train = {k: v for k, v in limits.items() if k in nums}
    assert train
    _, ok = bench_run.judge(nums, train)
    assert not ok
