"""The correctness check at a size a test run holds, on the CPU: a whole
run of each cell (set-up, window, reference) with the look for a chip
skipped, sound and with the timed path broken underneath; and the
control, the reference in float8 learner copies put in the program's
place. A sound run has to come out correct, each fault and the control
not, under the cell's own limits. A training cell answers nothing that a
token could be altered in, and a cell on one chip has no exchange
between chips to leave out. Half of the batch left out is not caught at
the cell's learning rate (its readings lie among the sound runs'; see
PERF.md), so it has no case here."""
import time
from dataclasses import replace

import jax
import pytest

from bench import correctness, program, run
from bench.reference import mavg

MODEL = {"num_layers": 2, "d_model": 256, "num_heads": 4, "vocab_size": 512,
         "ssm_expand": 2, "ssm_conv": 4, "slstm_every": 2, "norm_eps": 1e-5,
         "tie_embeddings": False, "dtype": "bfloat16"}
SEED = 2 ** 31 + 2718


def tiny(cell):
    spec = run.load_cell(cell)
    spec["config"] = {**spec["config"], "full_width": False, "model": MODEL}
    spec["traffic"] = {**spec["traffic"], "batch": 2, "seq": 64}
    return spec


def unchanged(trainer):
    """Each step hands back the state it was given (its metrics still
    computed)."""
    step = trainer._step_fn

    def broken(state, batches, lr):
        return replace(state, step=state.step + 1), step(state, batches,
                                                         lr=lr)[1]

    trainer._step_fn = broken


CASES = [("xlstm350m_1chip_k8", None, True),
         ("xlstm350m_1chip_k8", unchanged, False)]


@pytest.mark.parametrize("cell,fault,expect", CASES,
                         ids=[f"{c}-{f.__name__ if f else 'sound'}"
                              for c, f, _ in CASES])
def test_run_is_judged(cell, fault, expect, monkeypatch):
    build = program.build_trainer

    def planted(*args, **kw):
        trainer, cfg = build(*args, **kw)
        if fault is not None:
            fault(trainer)
        return trainer, cfg

    monkeypatch.setattr(program, "build_trainer", planted)
    result = run.run_cell(tiny(cell), SEED, 0.5, False,
                          t_start=time.perf_counter())
    assert result["correct"] is expect, result["checks"]
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == set(
        run.load_cell(cell)["checks"]["limits"])


@pytest.mark.parametrize("cell", ["xlstm350m_1chip_k8"])
def test_control_fails(cell):
    spec = tiny(cell)
    job = run.job_of(spec["config"], spec["traffic"])
    ws, salt = program.seeds(SEED)
    steps = spec["traffic"]["check_steps"]
    ref = mavg.run(MODEL, job, ws, salt, steps)
    control = mavg.run(MODEL, job, ws, salt, steps, storage="float8_e4m3fn")
    numbers = correctness.compare(control, ref)
    assert not correctness.judge(numbers, spec["checks"]["limits"]), numbers


def test_numbers_of_hand_readings():
    """Each leaf's gap over the larger of its reference norm and the
    median leaf's; a leaf whose first local gradient is nought to rounding
    left out; a reading that is not finite fails every number it enters."""
    ref = {"loss": [11.0, 10.0], "local_grad": {"a": 1.0, "b": 2.0,
                                                 "c": 1e-9},
           "first_grad": {"a": 4.0, "b": 1.0, "c": 9.0},
           "first_move": {"a": 2.0, "b": 0.5, "c": 9.0},
           "change": {"a": 1.0, "b": 1.0, "c": 9.0}}
    prog = {**ref, "loss": [11.5, 10.0],
            "first_move": {"a": 3.0, "b": 0.5, "c": 1.0}}
    n = correctness.compare(prog, ref)
    assert n["loss_gap"] == 0.5
    assert n["first_move_gap.a"] == 0.5 and n["first_move_gap.b"] == 0.0
    assert "first_move_gap.c" not in n
    assert n["first_move_gap"] == 0.5 and n["first_move_gap_median"] == 0.25
    assert n["first_grad_gap"] == 0.0 and n["change_gap_median"] == 0.0
    bad = {**prog, "first_move": {"a": float("nan"), "b": 0.5, "c": 1.0}}
    n = correctness.compare(bad, ref)
    assert n["first_move_gap"] == n["first_move_gap_median"] == float("inf")
    assert not correctness.judge(n, {"first_move_gap_median": 1e9})


def test_movement_leaves_out_the_rounding_of_the_start():
    """The learners' movement read from the program's state after one
    meta step, v1 + w0 - bfloat16(w0), is their mean's distance from the
    bfloat16 copy they started at, leaf by leaf."""
    import ml_dtypes
    import numpy as np
    from types import SimpleNamespace

    spec = SimpleNamespace(paths=["a", "b"], offsets=[0, 3], sizes=[3, 2])
    w0 = np.array([1.001, 2.0003, -3.0007, 0.5001, 0.2501], np.float32)
    start = w0.astype(ml_dtypes.bfloat16).astype(np.float32)
    move = np.array([3e-3, -4e-3, 0.0, 1e-3, 0.0], np.float32)
    v1 = (start + move) - w0
    got = program._leaf_norms(spec, v1, w0)
    assert got["a"] == pytest.approx(5e-3, rel=1e-4)
    assert got["b"] == pytest.approx(1e-3, rel=1e-3)
    # v1 alone holds the rounding of w0 too
    assert abs(program._leaf_norms(spec, v1)["a"] - got["a"]) > 1e-4
