"""repro.obs: tracing core, privacy ledger, and the zero-overhead contract.

The load-bearing promises (DESIGN.md §11):

  * spans nest and order correctly, and the recorder survives concurrent
    writers (the serve CLI's trainer thread + decode loop);
  * the ledger's content-hash chain detects any tamper, and its per-round
    cumulative ε is exactly the arm accountant's ε — the ledger is an
    audit of the accountant, not a second accountant;
  * enabling recording adds ZERO jit dispatches to the fused round loop
    (the O(1)-dispatch contract of DESIGN.md §7 is recording-invariant).
"""

import json
import threading

import jax
import numpy as np
import pytest

import repro.arms as arms
import repro.obs as obs
from repro.instrument import (
    instrumented_jit,
    jit_dispatches,
    reset_jit_dispatches,
)
from repro.obs.convert import chrome_trace, validate_chrome_trace
from repro.obs.ledger import GENESIS, LedgerError, PrivacyLedger, entry_id
from repro.obs.recorder import EventStreamError, Recorder, validate_events
from repro.sim import nodes_from_trace
from repro.sim.nodes import heterogeneous_trace

from test_arms_equivalence import (
    _cfg,
    _ideal_nodes,
    _ideal_topology,
    _make_model,
    _silos,
)


@pytest.fixture(autouse=True)
def _no_global_recorder():
    """Every test starts and ends with recording off."""
    obs.disable()
    yield
    obs.disable()


# -- tracing core -------------------------------------------------------------


def test_span_nesting_depth_and_ordering():
    rec = Recorder()
    with rec.span("outer", cat="t"):
        with rec.span("inner", cat="t", k=1):
            pass
        with rec.span("inner2", cat="t"):
            pass
    evs = [e for e in rec.events() if e["type"] == "span"]
    by_name = {e["name"]: e for e in evs}
    # children close before the parent: completion order is inner..outer
    assert [e["name"] for e in evs] == ["inner", "inner2", "outer"]
    assert by_name["outer"]["depth"] == 0
    assert by_name["inner"]["depth"] == 1
    assert by_name["inner2"]["depth"] == 1
    # child intervals lie inside the parent interval
    o, i = by_name["outer"], by_name["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-9
    assert i["args"] == {"k": 1}
    validate_events(rec.events())


def test_counters_accumulate_and_gauges_record():
    rec = Recorder()
    rec.counter("c", 2)
    rec.counter("c", 3, tag="x")
    rec.gauge("g", 7.5)
    totals = rec.counter_totals()
    assert totals["c"] == 5
    evs = rec.events()
    counters = [e for e in evs if e["type"] == "counter"]
    assert [e["total"] for e in counters] == [2, 5]
    gauge = next(e for e in evs if e["type"] == "gauge")
    assert gauge["value"] == 7.5
    validate_events(evs)


def test_disabled_api_is_a_noop():
    assert obs.recorder() is None
    assert obs.now() is None
    ctx = obs.span("x")
    assert ctx is obs.span("y")  # the one shared nullcontext
    with ctx:
        pass
    obs.counter("c")
    obs.gauge("g", 1.0)
    obs.complete("x", None)


def test_recording_context_restores_previous_state():
    with obs.recording() as rec:
        assert obs.recorder() is rec
        with obs.recording() as rec2:
            assert obs.recorder() is rec2
        assert obs.recorder() is rec
    assert obs.recorder() is None


def test_recorder_thread_safety_stress():
    """Trainer-thread + decode-loop shape: concurrent spans and counters
    from many threads land without loss or interleaving corruption."""
    rec = Recorder()
    n_threads, n_iter = 8, 200

    def work(tid):
        for i in range(n_iter):
            with rec.span("w", cat="stress", tid_arg=tid):
                rec.counter("ticks", 1)

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert rec.counter_totals()["ticks"] == n_threads * n_iter
    spans = [e for e in rec.events() if e["type"] == "span"]
    assert len(spans) == n_threads * n_iter
    # per-thread depth tracking: no cross-thread depth bleed
    assert all(e["depth"] == 0 for e in spans)
    validate_events(rec.events())


def test_instrumented_jit_dispatch_count_is_thread_safe():
    """The satellite fix: an unguarded += would lose ticks here."""
    import jax.numpy as jnp

    f = instrumented_jit(lambda x: x + 1)
    f(jnp.zeros(()))  # compile outside the timed region
    reset_jit_dispatches()
    n_threads, n_iter = 8, 50

    def work():
        for _ in range(n_iter):
            f(jnp.zeros(()))

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert jit_dispatches() == n_threads * n_iter


def test_instrumented_jit_feeds_obs_counter():
    import jax.numpy as jnp

    f = instrumented_jit(lambda x: x * 2)
    f(jnp.ones(()))  # compile outside the recorded region
    reset_jit_dispatches()
    with obs.recording() as rec:
        f(jnp.ones(()))
        f(jnp.ones(()))
    # the span count is the recorded dispatch count; no counter repeats it
    assert _span_count(rec, "jit_dispatch") == jit_dispatches() == 2
    assert "jit_dispatches" not in rec.counter_totals()


def test_event_stream_validation_catches_corruption():
    rec = Recorder()
    rec.counter("c", 1)
    rec.counter("c", 1)
    evs = [dict(e) for e in rec.events()]
    evs[-1]["total"] = 99.0  # break the running sum
    with pytest.raises(EventStreamError):
        validate_events(evs)


# -- chrome trace conversion --------------------------------------------------


def test_chrome_trace_conversion(tmp_path):
    rec = Recorder()
    with rec.span("a", cat="t"):
        rec.counter("c", 1)
    doc = chrome_trace(rec.events())
    phs = {e["ph"] for e in doc["traceEvents"]}
    assert "X" in phs and "C" in phs
    x = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert x["name"] == "a" and x["dur"] >= 0
    p = tmp_path / "trace.json"
    p.write_text(json.dumps(doc))
    assert validate_chrome_trace(p)["trace_events"] == len(doc["traceEvents"])


# -- privacy ledger -----------------------------------------------------------


def _toy_rounds(ledger, rounds=3, h=4, eps_step=0.5):
    for t in range(rounds):
        ledger.record_round(
            round=t, arm="decaph", backend="ideal", hospitals=h,
            cohort=range(h), delivered=range(h),
            epsilon=(t + 1) * eps_step, delta=1e-5,
            sampling_rate=0.1, participation_rate=1.0,
            noise_multiplier=0.8, bytes_up=100.0,
        )


def test_ledger_chain_validates_and_summarizes():
    led = PrivacyLedger()
    _toy_rounds(led)
    entries = led.entries()
    assert entries[0]["prev"] == GENESIS
    summary = obs.validate_entries(entries)
    assert summary["hospitals"] == 4 and summary["rounds"] == 3
    assert summary["final_eps"] == {i: pytest.approx(1.5) for i in range(4)}
    assert obs.per_hospital_epsilon(entries) == {
        i: pytest.approx(1.5) for i in range(4)
    }


@pytest.mark.parametrize("tamper", ["eps", "reorder", "drop", "prev"])
def test_ledger_tamper_detection(tamper):
    led = PrivacyLedger()
    _toy_rounds(led)
    entries = [dict(e) for e in led.entries()]
    if tamper == "eps":
        entries[5]["eps"] = 0.0            # rewrite history, keep the id
    elif tamper == "reorder":
        entries[2], entries[3] = entries[3], entries[2]
    elif tamper == "drop":
        del entries[4]
    elif tamper == "prev":
        entries[6]["prev"] = "f" * 16
    with pytest.raises(LedgerError):
        obs.validate_entries(entries)


def test_ledger_recompute_id_detects_field_rewrite():
    led = PrivacyLedger()
    _toy_rounds(led, rounds=1)
    e = dict(led.entries()[0])
    e["bytes_up"] = 1e9
    assert entry_id(e) != e["id"]


def test_ledger_jsonl_roundtrip(tmp_path):
    led = PrivacyLedger()
    _toy_rounds(led)
    p = tmp_path / "ledger.jsonl"
    led.write_jsonl(p)
    back = obs.read_entries(p)
    assert back == led.entries()
    obs.validate_entries(back)


# -- ledger vs accountant (the acceptance criterion) --------------------------


def _sim_nodes(h):
    return nodes_from_trace(heterogeneous_trace(h))


def test_ledger_epsilon_matches_accountant_per_round():
    """decaph/sim/H=5: every ledger entry's ε equals the accountant's ε
    at that round (RoundLog pins it), and the cumulative per-hospital ε
    equals the run's final ε — the shared-accountant semantics of the
    paper (one ε over the aggregate dataset, every hospital covered)."""
    h = 5
    cfg = _cfg(rounds=4, use_secagg=True)
    with obs.recording() as rec:
        rep = arms.run("decaph", _make_model(5), _silos(sizes=(120,) * h),
                       cfg, backend="sim", nodes=_sim_nodes(h))
    entries = rec.ledger.entries()
    obs.validate_entries(entries)
    assert rep.rounds_completed == 4
    assert len(entries) == h * rep.rounds_completed
    eps_by_round = {log.round: log.epsilon for log in rep.logs}
    for e in entries:
        assert e["hospital"] in range(h)
        assert e["eps"] == pytest.approx(eps_by_round[e["round"]], rel=1e-9)
        assert e["arm"] == "decaph" and e["backend"] == "sim"
        assert e["member"] and e["delivered"]
        assert e["bytes_up"] > 0
    per_h = obs.per_hospital_epsilon(entries)
    assert set(per_h) == set(range(h))
    for hosp in range(h):
        assert per_h[hosp] == pytest.approx(rep.epsilon, rel=1e-9)


def test_ledger_ideal_backend_matches_sim_epsilon():
    cfg = _cfg(rounds=3)
    with obs.recording() as rec:
        rep = arms.run("decaph", _make_model(5), _silos(), cfg)
    entries = rec.ledger.entries()
    assert len(entries) == 4 * 3  # H=4 silos x 3 rounds
    assert obs.per_hospital_epsilon(entries)[0] == pytest.approx(rep.epsilon)


# -- the zero-overhead contract ----------------------------------------------


@pytest.mark.parametrize("use_secagg", [False, True])
def test_recording_adds_zero_jit_dispatches(use_secagg):
    """The pinned overhead bound: the fused round loop launches exactly as
    many compiled programs with recording on as off, and trains to the same
    parameters."""
    cfg = _cfg(rounds=3, use_secagg=use_secagg)
    model, silos = _make_model(5), _silos()

    arms.run("decaph", model, silos, cfg)  # warm the compile caches
    reset_jit_dispatches()
    off = arms.run("decaph", model, silos, cfg)
    baseline = jit_dispatches()

    reset_jit_dispatches()
    with obs.recording() as rec:
        on = arms.run("decaph", model, silos, cfg)
    recorded = jit_dispatches()

    assert baseline > 0
    assert recorded == baseline
    # the recorder's spans count the same launches as the process counter
    assert _span_count(rec, "jit_dispatch") == recorded
    for a, b in zip(jax.tree_util.tree_leaves(off.params),
                    jax.tree_util.tree_leaves(on.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the split of a DeCaPH round ----------------------------------------------

# each once per stepped round of an ``ideal`` DeCaPH run with SecAgg on
_ROUND_SPANS = ("round", "fused_round", "fused_round.fetch", "aggregate",
                "secagg.sizes", "secagg.upload", "secagg.encode",
                "secagg.masks", "secagg.sum", "update", "account")


def _spans(rec, name=None):
    return [e for e in rec.events() if e["type"] == "span"
            and (name is None or e["name"] == name)]


def _span_count(rec, name):
    return len(_spans(rec, name))


def test_secagg_round_spans_split_the_aggregate():
    """Each span of the round's split occurs once per round, nested under
    the span that causes it, and its ``bytes`` are what crosses between
    host and device: the losses only, SecAgg's field arithmetic runs on
    the device."""
    rounds, h = 2, 4
    model = _make_model(5)
    with obs.recording() as rec:
        rep = arms.run("decaph", model, _silos(),
                       _cfg(rounds=rounds, use_secagg=True))
    assert rep.rounds_completed == rounds
    for name in _ROUND_SPANS:
        assert _span_count(rec, name) == rounds, name
    depth = {e["name"]: e["depth"] for e in _spans(rec)}
    assert depth["secagg.encode"] > depth["secagg.upload"]
    assert depth["secagg.masks"] > depth["secagg.upload"]
    assert depth["secagg.upload"] > depth["aggregate"] > depth["round"]
    for name in ("secagg.sizes", "secagg.sum", "update"):
        assert depth[name] == depth["aggregate"] + 1, name
    assert depth["fused_round.fetch"] == depth["fused_round"] + 1
    assert depth["account"] == depth["round"] + 1

    leaves = jax.tree_util.tree_leaves(model.init_fn(jax.random.key(0)))
    length = sum(np.asarray(x).size for x in leaves)
    f32 = np.dtype(np.float32).itemsize
    for e in _spans(rec, "secagg.upload"):
        assert e["args"] == {"participants": h, "length": length}
    # the net masks stay on the device
    for e in _spans(rec, "secagg.masks"):
        assert e["args"] == {"pairs": h * (h - 1) // 2, "bytes": 0}
    # only the losses come to the host; the payload stack stays put
    assert [e["args"]["bytes"] for e in _spans(rec, "fused_round.fetch")] \
        == [h * f32] * rounds
    # the field sum is decoded on the device
    assert [e["args"]["bytes"] for e in _spans(rec, "secagg.sum")] \
        == [0] * rounds


@pytest.mark.parametrize("h", [2, 5, 8])
def test_ideal_secagg_keeps_the_payload_stack_on_device(h):
    """With SecAgg on, the idealized backend hands the cohort's payload
    stack to the field programs where the cohort program left it: only the
    losses are fetched, neither masks nor sums cross, and the parameters
    after 3 rounds are the sim backend's (per-participant ciphertexts,
    dropout-robust session) bit for bit."""
    silos = _silos(sizes=(120,) * h)
    model = _make_model(5)
    cfg = _cfg(rounds=3, use_secagg=True)
    with obs.recording() as rec:
        ideal = arms.run("decaph", model, silos, cfg)
    simmed = arms.run("decaph", model, silos, cfg, backend="sim",
                      nodes=_ideal_nodes(h), topo=_ideal_topology("full", h))
    assert ideal.rounds_completed == simmed.rounds_completed == 3
    f32 = np.dtype(np.float32).itemsize
    assert [e["args"]["bytes"] for e in _spans(rec, "fused_round.fetch")] \
        == [h * f32] * 3
    for name in ("secagg.masks", "secagg.sum"):
        assert [e["args"]["bytes"] for e in _spans(rec, name)] == [0] * 3
    for a, b in zip(jax.tree_util.tree_leaves(ideal.params),
                    jax.tree_util.tree_leaves(simmed.params)):
        assert isinstance(a, jax.Array)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_no_secagg_span_with_secagg_off():
    with obs.recording() as rec:
        arms.run("decaph", _make_model(5), _silos(), _cfg(rounds=2))
    names = {e["name"] for e in _spans(rec)}
    assert not {n for n in names if n.startswith("secagg.")}
    assert {"fused_round.fetch", "update", "account"} <= names
    # only the losses come to the host
    assert {e["args"]["bytes"] for e in _spans(rec, "fused_round.fetch")} \
        == {4 * np.dtype(np.float32).itemsize}


def test_sim_secagg_upload_once_per_round_at_one_depth():
    h = 5
    with obs.recording() as rec:
        rep = arms.run("decaph", _make_model(5), _silos(sizes=(120,) * h),
                       _cfg(rounds=3, use_secagg=True), backend="sim",
                       nodes=_sim_nodes(h))
    assert _span_count(rec, "secagg.upload") == rep.rounds_completed == 3
    assert _span_count(rec, "secagg.sum") == 3
    assert _span_count(rec, "account") == 3
    depths: dict[str, set[int]] = {}
    for e in _spans(rec):
        depths.setdefault(e["name"], set()).add(e["depth"])
    assert all(len(d) == 1 for d in depths.values()), depths


# -- serve + metrics ----------------------------------------------------------


def test_serve_engine_emits_obs_counters():
    from repro.serve.engine import ServeConfig, ServeEngine, batch_generate

    with obs.recording() as rec:
        engine = ServeEngine(ServeConfig(slots=2, max_len=32, seed=0))
        prompts = np.ones((2, 4), np.int32)
        batch_generate(engine, prompts, gen=3)
    totals = rec.counter_totals()
    assert _span_count(rec, "serve.admit") == 2
    assert _span_count(rec, "serve.decode_step") == engine.decode_steps
    assert totals["serve.evictions"] == 2
    assert not {"serve.admits", "serve.decode_steps"} & set(totals)
    validate_events(rec.events())


def test_metrics_survive_degenerate_traces():
    from repro.serve.metrics import render_markdown, summarize
    from repro.serve.traffic import TraceResult

    empty = TraceResult(completed=[], steps=[], wall=0.0, swaps=0,
                        decode_steps=0, decode_dispatches=0,
                        admit_dispatches=0)
    row = summarize(empty, slots=0, rate=1.0)
    assert row["throughput_tok_s"] == 0.0
    assert row["occupancy"] == 0.0
    assert row["dispatches_per_step"] == 0.0
    assert row["ttft_p95_ms"] == 0.0 and row["tpot_p95_ms"] == 0.0
    md = render_markdown([row], title="t")
    assert "TTFT p95" in md and "TPOT p95" in md
    # pre-p95 rows (the committed BENCH_serve.json) still render
    old = {k: v for k, v in row.items() if "p95" not in k}
    assert render_markdown([old], title="t").count("|")


# -- export + CLI -------------------------------------------------------------


def test_export_and_cli_validate_roundtrip(tmp_path):
    from repro.obs.cli import main as obs_main

    out = tmp_path / "obs"
    cfg = _cfg(rounds=2)
    with obs.recording() as rec:
        arms.run("decaph", _make_model(5), _silos(), cfg)
        paths = obs.export(out, rec)
    assert all(p.exists() for p in paths.values())
    assert obs_main(["--validate", str(out)]) == 0
    assert obs_main([str(out)]) == 0  # summary mode

    # corrupt one ledger line -> the chain breaks -> exit 1
    lines = paths["ledger"].read_text().splitlines()
    tampered = json.loads(lines[2])
    tampered["eps"] = 0.0
    lines[2] = json.dumps(tampered)
    paths["ledger"].write_text("\n".join(lines) + "\n")
    assert obs_main(["--validate", str(out)]) == 1


def test_export_without_recorder_raises(tmp_path):
    with pytest.raises(RuntimeError):
        obs.export(tmp_path / "nope")


def test_cli_to_chrome(tmp_path):
    from repro.obs.cli import main as obs_main

    rec = Recorder()
    with rec.span("a"):
        pass
    events = tmp_path / "events.jsonl"
    rec.write_jsonl(events)
    out = tmp_path / "converted.json"
    assert obs_main(["--to-chrome", str(events), "--out", str(out)]) == 0
    assert validate_chrome_trace(out)["trace_events"] >= 1


# -- sweep cells --------------------------------------------------------------


def test_sweep_cell_phase_breakdown():
    from repro.scenarios.executor import run_spec
    from repro.scenarios.presets import get_preset

    spec = get_preset("gemini-5hospital").replace(rounds=2)
    with obs.recording():
        row = run_spec(spec)
    assert "phase_seconds" in row
    assert row["phase_seconds"]  # at least one phase accumulated time
    assert all(v >= 0 for v in row["phase_seconds"].values())
    assert "noise_topups" in row and "host_seconds" in row

    row_off = run_spec(spec)
    assert "phase_seconds" not in row_off
