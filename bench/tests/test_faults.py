"""The comparison that decides ``correct`` catches the faults each cell
can have: the harness's look for a chip is skipped, the rest of a run
is driven at the smoke sizes with the timed path broken underneath,
and ``correct`` has to come out false."""

import time

import pytest

from bench.lib import registry

SERVE = [c["name"] for c in registry.benchmark()["workloads"]
         if registry.traffic(c["traffic"])["kind"] == "serve"]
TRAIN = [c["name"] for c in registry.benchmark()["workloads"]
         if registry.traffic(c["traffic"])["kind"] == "train"]


def _run(cell, hooks):
    import jax

    from bench import run
    return run.run_cell(cell, 2 ** 31 + 5, 0.3, False, jax.devices(),
                        t_start=time.perf_counter(), smoke=True,
                        hooks=hooks)


def _answer_altered(fwd):
    def broken(*args):
        out, hits, gidx = fwd(*args)
        return out.at[0].add(1e-2), hits, gidx
    return broken


def _half_batch_left_out(fwd):
    def broken(*args):
        out, hits, gidx = fwd(*args)
        return out.at[out.shape[0] // 2:].set(0.0), hits, gidx
    return broken


def _state_unchanged(step):
    return lambda state, batch: (state, step(state, batch)[1])


def _half_batch_mean(step):
    return lambda state, batch: step(
        state, {k: v[:v.shape[0] // 2] for k, v in batch.items()})


@pytest.mark.parametrize("cell", SERVE)
@pytest.mark.parametrize("fault", [_answer_altered, _half_batch_left_out])
def test_serve_fault_is_not_correct(cell, fault):
    res = _run(cell, {"forward": fault})
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", TRAIN)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch_mean])
def test_train_fault_is_not_correct(cell, fault):
    res = _run(cell, {"step": fault})
    assert res["correct"] is False, res["checks"]
