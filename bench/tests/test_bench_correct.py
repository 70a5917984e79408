"""``correct`` at a size a test run holds, on the CPU: a sound run passes
each cell's limits; the control (the reference in bfloat16, in the
program's place) fails them; and a run with a fault planted under the timed
path (``bench/faults.py``) comes out not correct."""

import json
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import cohort, compare, faults, harness

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2**31 + 11


def tiny(name: str) -> harness.Cell:
    """The cell with its configuration cut to a test's size by its kind."""
    cell = harness.load_cell(name)
    cell.config = harness.load_kind(cell.config).tiny(cell.config)
    return cell


def run(cell: harness.Cell) -> dict:
    return harness.run_cell(cell, SEED, 0.0, False, time.perf_counter())


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    result = run(tiny(name))
    assert result["correct"] is True
    assert list(result)[-1] == "compared"
    assert result["attempted"] >= harness.FOLLOWED_ROUNDS - 1
    assert result["failed"] == 0
    assert set(result["metrics"]) == {"round_ms", "setup_s"}


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = tiny(name)
    raw = cohort.cohort(cell.config, cell.traffic, SEED)
    values = harness.check(cell, raw, SEED,
                              harness.control_outputs(cell, raw, SEED))
    assert not compare.judge(values, cell.limits)


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_makes_the_run_not_correct(name, fault):
    with faults.FAULTS[fault]():
        result = run(tiny(name))
    assert result["correct"] is False


def test_seed_draws_the_cohort_and_weights_not_the_stream():
    """Two seeds give two cohorts and two sets of initial weights, and one
    program stream, so no seed compiles a round step of its own."""
    cell = tiny(CELLS[0])
    built = [harness.build_program(cell, cohort.cohort(cell.config,
                                                     cell.traffic, s), s)
             for s in (SEED, SEED + 1)]
    (m1, h1, c1), (m2, h2, c2) = built
    assert c1.seed == c2.seed == harness.STREAM_SEED
    assert not np.array_equal(h1[0].x, h2[0].x)
    w1, w2 = (np.concatenate([np.ravel(a) for a in jax.tree_util.tree_leaves(
        m.init_fn(jax.random.key(0)))]) for m in (m1, m2))
    assert not np.array_equal(w1, w2)
