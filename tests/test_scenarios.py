"""repro.scenarios: spec round-trips, sweep cache semantics, grids, churn,
the fedprox satellite arm, and the scaling-law report layer."""

import json
import logging

import numpy as np
import pytest

import repro.arms as arms
from repro.scenarios import (
    ResultCache,
    ScenarioSpec,
    SweepGrid,
    all_presets,
    fit_power_law,
    get_preset,
    get_sweep,
    markdown_report,
    run_spec,
    run_sweep,
    scaling_laws,
)
from repro.sim import LinkSchedule, Topology, nodes_from_trace

# -- ScenarioSpec -------------------------------------------------------------


def test_spec_json_roundtrip_is_identity():
    spec = get_preset("gemini-5hospital-churn")
    back = ScenarioSpec.from_json(spec.to_json())
    assert back == spec
    assert back.spec_hash() == spec.spec_hash()
    # a second decode of the re-encoded form is stable too
    assert ScenarioSpec.from_json(back.to_json()) == spec


def test_spec_hash_excludes_labels_but_covers_semantics():
    spec = ScenarioSpec(name="a", tags=("x",))
    relabeled = spec.replace(name="b", tags=("y", "z"))
    assert relabeled.spec_hash() == spec.spec_hash()
    for field, value in (("seed", 7), ("hospitals", 3), ("arm", "fl"),
                         ("noise_multiplier", 1.3), ("backend", "ideal"),
                         ("topology", {"kind": "ring"})):
        assert spec.replace(**{field: value}).spec_hash() != spec.spec_hash()


def test_spec_validation_rejects_bad_values():
    with pytest.raises(ValueError, match="task"):
        ScenarioSpec(task="mri")
    with pytest.raises(ValueError, match="backend"):
        ScenarioSpec(backend="cloud")
    with pytest.raises(ValueError, match="hospitals"):
        ScenarioSpec(hospitals=0)
    with pytest.raises(ValueError, match="straggler_ratio"):
        ScenarioSpec(straggler_ratio=1.5)
    with pytest.raises(ValueError, match="nodes trace"):
        ScenarioSpec(hospitals=3, nodes=[{"throughput": 10.0}] * 2)
    with pytest.raises(ValueError, match="unknown ScenarioSpec fields"):
        ScenarioSpec.from_dict({"task": "gemini", "bogus": 1})


def test_preset_library_covers_paper_case_studies():
    catalogue = all_presets()
    for task in ("gemini", "pancreas", "xray"):
        for size in ("small", "medium", "full"):
            assert f"{task}-{size}" in catalogue
    assert catalogue["gemini-full"].features is None  # task default: 436
    with pytest.raises(KeyError, match="unknown preset"):
        get_preset("nope")


# -- SweepGrid ----------------------------------------------------------------


def test_sweep_grid_expands_axis_product():
    grid = SweepGrid(
        "t", ScenarioSpec(name="base", tags=("base",)),
        {"arm": ["fl", "decaph"], "hospitals": [3, 5, 7]},
    )
    specs = grid.specs()
    assert len(specs) == grid.size() == 6
    assert {(s.arm, s.hospitals) for s in specs} == {
        (a, h) for a in ("fl", "decaph") for h in (3, 5, 7)
    }
    # names are self-describing and unique; sweep tag is appended
    assert len({s.name for s in specs}) == 6
    assert all("sweep:t" in s.tags and "base" in s.tags for s in specs)


def test_sweep_grid_rejects_unknown_axis():
    with pytest.raises(ValueError, match="unknown spec fields"):
        SweepGrid("t", ScenarioSpec(), {"bogus_axis": [1]})


def test_named_sweeps_enumerate_live_arm_registry():
    mini = get_sweep("capacity-mini")
    assert set(mini.axes["arm"]) == set(arms.names())  # fedprox included
    assert mini.size() >= 12


# -- result cache -------------------------------------------------------------


_CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _fake_result(spec, **overrides):
    out = {
        "name": spec.name, "key": spec.spec_hash(), "task": spec.task,
        "arm": spec.arm, "backend": spec.backend,
        "hospitals": spec.hospitals, "model_size": spec.model_size,
        "model_params": 9, "rounds_completed": spec.rounds,
        "epsilon": 1.0, "mean_loss": 0.5, "accuracy": 0.9,
        "wall_clock": 1.0, "bytes_on_wire": 100.0, "dropout_events": 0,
        "recoveries": 0, "lost_rounds": 0, "events": 10,
        "host_seconds": 0.01,
        "device": dict(_CPU),
    }
    out.update(overrides)
    return out


def test_cache_hit_skips_executor_and_changed_spec_misses(tmp_path):
    cache = ResultCache(tmp_path)
    spec = ScenarioSpec(name="cell", arm="fl", rounds=2)
    calls = []

    def counting_runner(s):
        calls.append(s.spec_hash())
        return _fake_result(s)

    first = run_sweep([spec], cache, runner=counting_runner)
    assert (first.hits, first.misses) == (0, 1) and len(calls) == 1

    again = run_sweep([spec], cache, runner=counting_runner)
    assert (again.hits, again.misses) == (1, 0)
    assert len(calls) == 1  # executor NOT invoked twice for the same spec
    assert again.results[0] == first.results[0]

    # a changed seed is a different cell: miss, executor runs
    reseeded = spec.replace(seed=99)
    third = run_sweep([spec, reseeded], cache, runner=counting_runner)
    assert (third.hits, third.misses) == (1, 1)
    assert len(calls) == 2 and calls[-1] == reseeded.spec_hash()


def test_cache_corrupted_entry_recomputed_with_warning(tmp_path, caplog):
    cache = ResultCache(tmp_path)
    spec = ScenarioSpec(name="cell", arm="fl", rounds=2)
    cache.put(spec, _fake_result(spec))
    cache.path(spec).write_text("{ not json")

    calls = []

    def counting_runner(s):
        calls.append(s.name)
        return _fake_result(s)

    with caplog.at_level(logging.WARNING, logger="repro.scenarios.cache"):
        outcome = run_sweep([spec], cache, runner=counting_runner)
    assert outcome.misses == 1 and calls == ["cell"]
    assert any("corrupted cache entry" in r.message for r in caplog.records)
    # the recompute repaired the entry
    assert cache.get(spec) is not None


def test_cache_rejects_key_mismatch_and_missing_fields(tmp_path, caplog):
    cache = ResultCache(tmp_path)
    spec = ScenarioSpec(name="cell", arm="fl")
    # entry whose key does not match the spec hash (stale/foreign file)
    cache.path(spec).write_text(json.dumps(
        {"schema": 1, "key": "deadbeef", "spec": {},
         "result": _fake_result(spec)}
    ))
    with caplog.at_level(logging.WARNING, logger="repro.scenarios.cache"):
        assert cache.get(spec) is None
    assert not cache.path(spec).exists()  # evicted
    # entry with a valid key but gutted result payload
    entry = {"schema": 1, "key": cache.key(spec), "spec": spec.to_dict(),
             "result": {"arm": "fl"}}
    cache.path(spec).write_text(json.dumps(entry))
    assert cache.get(spec) is None


def test_cache_row_from_one_device_is_a_miss_on_another(tmp_path,
                                                         monkeypatch):
    import repro.scenarios.cache as cache_lib

    spec = ScenarioSpec(name="cell", arm="fl", rounds=2)
    monkeypatch.setattr(cache_lib, "device_identity", lambda: dict(_CPU))
    ResultCache(tmp_path).put(spec, _fake_result(spec))
    assert ResultCache(tmp_path).get(spec) is not None  # same device: hit

    monkeypatch.setattr(cache_lib, "device_identity", lambda: dict(_TPU))
    on_chip = ResultCache(tmp_path)
    assert on_chip.get(spec) is None
    calls = []

    def chip_runner(s):
        calls.append(s.name)
        return _fake_result(s, device=dict(_TPU))

    outcome = run_sweep([spec], on_chip, runner=chip_runner)
    assert (outcome.hits, outcome.misses) == (0, 1) and calls == ["cell"]
    assert outcome.results[0]["device"] == _TPU
    assert len(on_chip) == 2  # both devices' rows are kept side by side


def test_cache_entry_without_device_is_evicted(tmp_path, caplog):
    cache = ResultCache(tmp_path)
    spec = ScenarioSpec(name="cell", arm="fl")
    row = _fake_result(spec)
    del row["device"]
    cache.put(spec, row)
    with caplog.at_level(logging.WARNING, logger="repro.scenarios.cache"):
        assert cache.get(spec) is None
    assert any("device" in r.message for r in caplog.records)


def test_sweep_runs_inline_off_the_cpu(tmp_path, monkeypatch, capsys):
    """A pool worker cannot use the accelerator the parent holds: off the
    CPU, --jobs > 1 runs every cell in this process and says so."""
    import repro.scenarios.cache as cache_lib
    import repro.scenarios.executor as executor_lib

    monkeypatch.setattr(cache_lib, "device_identity", lambda: dict(_TPU))

    def no_pool(*a, **k):
        raise AssertionError("a process pool was started off the CPU")

    monkeypatch.setattr(executor_lib, "ProcessPoolExecutor", no_pool)
    ran = []
    monkeypatch.setattr(executor_lib, "run_spec",
                        lambda s: ran.append(s.name) or _fake_result(s))
    specs = [ScenarioSpec(name=f"c{i}", arm="fl", seed=i) for i in range(3)]
    outcome = run_sweep(specs, ResultCache(tmp_path), jobs=4)
    assert outcome.misses == 3 and ran == ["c0", "c1", "c2"]
    assert "inline" in capsys.readouterr().err


# -- end-to-end: a real (tiny) sweep through the cache ------------------------


def test_mini_sweep_end_to_end_cached(tmp_path):
    specs = SweepGrid(
        "e2e",
        ScenarioSpec(task="gemini", model_size="small", features=6,
                     examples=160, rounds=2, batch_size=24, backend="sim",
                     use_secagg=False),
        {"arm": ["fl"], "hospitals": [3, 4]},
    ).specs()
    cache = ResultCache(tmp_path)
    first = run_sweep(specs, cache, jobs=1)
    assert (first.hits, first.misses) == (0, 2)
    for cell in first.results:
        assert cell["rounds_completed"] == 2
        assert cell["wall_clock"] > 0 and cell["bytes_on_wire"] > 0
        assert 0.0 <= cell["accuracy"] <= 1.0

    second = run_sweep(specs, cache, jobs=1)
    assert (second.hits, second.misses) == (2, 0)
    assert second.results == first.results

    laws = scaling_laws(first.results)
    assert "fl" in laws["bytes_vs_hospitals"]
    md = markdown_report("e2e", first.results, laws)
    assert "| fl |" in md and "Bytes on wire vs cohort size" in md


@pytest.mark.slow
def test_pool_sweep_caches_survivors_when_one_cell_fails(tmp_path):
    """Process-pool path: a failing cell raises AFTER sibling results are
    cached, so the re-run resumes from every cell that succeeded."""
    good = ScenarioSpec(name="good", task="gemini", model_size="small",
                        features=6, examples=160, rounds=2, batch_size=24,
                        backend="sim", use_secagg=False, arm="fl")
    bad = good.replace(name="bad", arm="no-such-arm")  # fails in the worker
    cache = ResultCache(tmp_path)
    with pytest.raises(KeyError, match="no-such-arm"):
        run_sweep([bad, good], cache, jobs=2)
    assert cache.get(good) is not None      # survivor was persisted
    assert cache.get(bad) is None
    resumed = run_sweep([good], cache, jobs=2)
    assert (resumed.hits, resumed.misses) == (1, 0)


def test_run_spec_executes_preset_on_ideal_backend():
    spec = get_preset("gemini-small").replace(
        backend="ideal", features=6, examples=160, rounds=2, batch_size=24,
        hospitals=3, use_secagg=False, arm="fl",
    )
    cell = run_spec(spec)
    assert cell["rounds_completed"] == 2
    assert cell["wall_clock"] == 0.0  # idealized: no systems story
    assert cell["model_params"] == 7  # w[6] + b


# -- LinkSchedule churn (satellite) ------------------------------------------


def test_link_schedule_from_trace_and_advance():
    topo = Topology.from_trace({
        "n": 3, "kind": "full",
        "default": {"bandwidth": 1e6, "latency": 0.01},
        "schedule": [
            {"t": 1.0, "link": "0-2", "bandwidth": 1e3, "latency": 0.5},
            {"t": 2.0, "link": "0-2", "down": True},
            {"t": 5.0, "link": "0-2", "bandwidth": 1e6, "latency": 0.01},
        ],
    })
    assert topo.transfer_time(0, 2, 1e3) == pytest.approx(0.011)
    assert topo.advance_to(1.0) == 1          # degrade fires
    assert topo.transfer_time(2, 0, 1e3) == pytest.approx(1.5)  # symmetric
    assert topo.advance_to(1.5) == 0          # idempotent between changes
    topo.advance_to(2.0)                      # edge removed
    assert not topo.has_edge(0, 2)
    assert topo.neighbors(0) == [1]
    topo.advance_to(10.0)                     # restored
    assert topo.has_edge(0, 2)
    assert topo.transfer_time(0, 2, 1e3) == pytest.approx(0.011)


def test_link_schedule_roundtrips_and_validates():
    sched = LinkSchedule.from_trace([
        {"t": 2.0, "link": "1-0", "down": True},
        {"t": 1.0, "link": "0-1", "bandwidth": 5.0, "latency": 0.1},
    ])
    assert [c.time for c in sched.changes] == [1.0, 2.0]  # time-sorted
    assert LinkSchedule.from_trace(sched.to_trace()).changes == sched.changes
    with pytest.raises(ValueError, match="schedule change on edge"):
        Topology.from_trace({
            "n": 2, "kind": "full",
            "schedule": [{"t": 1.0, "link": "0-5", "down": True}],
        })


def test_churn_severs_uploads_and_triggers_recovery():
    """Killing every link to one hospital mid-run behaves like a dropout:
    decaph keeps stepping via Shamir recovery, and restoring the links
    brings the hospital back into the rounds."""
    from repro.models.tabular import linear_model

    rng = np.random.default_rng(0)
    w_true = np.array([1.5, -2.0, 1.0, 0.0, 0.5])
    silos = []
    for i in range(4):
        x = rng.normal(0.1 * i, 1.0, (120, 5)).astype(np.float32)
        y = (x @ w_true + rng.normal(0, 0.2, 120) > 0).astype(np.float32)
        silos.append(arms.Participant(x, y))
    model = linear_model(5)
    cfg = arms.ArmConfig(rounds=6, batch_size=32, lr=0.3, seed=0)
    nodes = nodes_from_trace([{"throughput": 200.0, "overhead": 0.02}] * 4)
    topo = Topology.from_trace({
        "n": 4, "kind": "full",
        "default": {"bandwidth": 1e5, "latency": 0.01},
        "schedule": [{"t": 0.5, "link": f"{i}-3", "down": True}
                     for i in range(3)],
    })
    rep = arms.run("decaph", model, silos, cfg, backend="sim",
                   nodes=nodes, topo=topo)
    assert rep.rounds_completed >= 4     # training survived the partition
    assert rep.recoveries >= 1           # severed upload recovered via Shamir


# -- fedprox (satellite) ------------------------------------------------------


def test_fedprox_registered_and_learns_on_both_backends():
    assert "fedprox" in arms.names()
    cls = arms.get("fedprox")
    assert cls.mode == "round" and cls.topology_kind == "star"

    rng = np.random.default_rng(1)
    w_true = np.array([1.5, -2.0, 1.0, 0.0, 0.5])
    silos = []
    for i in range(4):  # heterogeneous silos: fedprox's home turf
        x = rng.normal(0.3 * i, 1.0, (110, 5)).astype(np.float32)
        y = (x @ w_true + rng.normal(0, 0.2, 110) > 0).astype(np.float32)
        silos.append(arms.Participant(x, y))
    from repro.models.tabular import linear_model, pooled_accuracy

    model = linear_model(5)
    cfg = arms.ArmConfig(rounds=6, batch_size=32, lr=0.3, seed=0,
                         use_secagg=False, fedprox_mu=0.1)
    rep = arms.run("fedprox", model, silos, cfg)
    assert rep.rounds_completed == 6
    assert pooled_accuracy(model, rep.params, silos) > 0.75
    # mu=0 with one pass matches plain FedAvg's trajectory shape (sanity:
    # the proximal term actually changes the update when mu > 0)
    rep0 = arms.run("fedprox", model, silos,
                    arms.ArmConfig(rounds=6, batch_size=32, lr=0.3, seed=0,
                                   use_secagg=False, fedprox_mu=0.0))
    la = np.asarray(rep.params["w"])
    lb = np.asarray(rep0.params["w"])
    assert not np.array_equal(la, lb)


# -- report layer -------------------------------------------------------------


def test_fit_power_law_recovers_known_exponent():
    xs = [3, 5, 10, 20]
    ys = [2.0 * x**1.5 for x in xs]
    fit = fit_power_law(xs, ys)
    assert fit["exponent"] == pytest.approx(1.5, abs=1e-9)
    assert fit["coefficient"] == pytest.approx(2.0, rel=1e-9)
    assert fit["r2"] == pytest.approx(1.0)
    assert fit_power_law([3, 3], [1.0, 2.0]) is None   # one distinct x
    assert fit_power_law([1, 2], [0.0, 1.0]) is None   # non-positive y
