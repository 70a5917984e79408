"""The trace reduction, on a trace recorded on one TPU v5e chip: about one
second of ``gemini-secagg`` rounds, with the program's spans of that run."""

import gzip
import json
from pathlib import Path

import pytest

from bench import trace_reduce

DATA = Path(__file__).with_name("data")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "gemini-secagg.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "gemini-secagg.xplane.pb.gz").read_bytes()))
    spans = json.loads((DATA / "gemini-secagg.spans.json").read_text())
    return path, spans["spans"], tuple(spans["window"])


def test_busy_union_and_idle_share(recorded):
    s = trace_reduce.reduce(*recorded)
    assert s.devices == 1
    assert s.window_s == pytest.approx(recorded[2][1] - recorded[2][0])
    assert 0 < s.busy_s < 0.1 * s.window_s   # a host-bound cell


def test_top_ops_are_named_by_program(recorded):
    s = trace_reduce.reduce(*recorded)
    names = [n for n, _ in s.top_ops]
    assert len(names) == 10 and all(":" in n for n in names)
    assert any(n.startswith("jit_dropped:") for n in names)
    assert not any(n.split(":")[1].startswith("while") for n in names)
    assert [t for _, t in s.top_ops] == sorted((t for _, t in s.top_ops),
                                               reverse=True)


def test_idle_gaps_are_named_by_host_span(recorded):
    s = trace_reduce.reduce(*recorded)
    assert len(s.gaps) == 10
    assert {n for n, _ in s.gaps} <= {"aggregate", "fused_round", "round",
                                      "host_rng.stack_poisson",
                                      "jit_dispatch"}
    assert s.gaps[0][0] == "aggregate"
    assert sum(t for _, t in s.gaps) <= s.window_s - s.busy_s


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]


def test_spans_are_aligned_by_fused_round_starts():
    spans = [{"name": "round", "ts": 0.9, "dur": 0.9, "depth": 1},
             {"name": "fused_round", "ts": 1.0, "dur": 0.5, "depth": 2},
             {"name": "fused_round", "ts": 2.0, "dur": 0.5, "depth": 2}]
    assert trace_reduce.clock_offset_ns([5e9 + 1e9, 5e9 + 2e9], spans) == 5e9


def test_the_offset_matches_the_recorded_run(recorded):
    path, spans, _ = recorded
    _, anchors = trace_reduce.read(path)
    starts = sorted(ev["ts"] for ev in spans
                    if ev["name"] == trace_reduce.ANCHOR)
    off = trace_reduce.clock_offset_ns(anchors, spans)
    # every matched span starts within a millisecond of its annotation
    assert max(abs(a - s * 1e9 - off) for a, s in zip(anchors, starts)) < 1e6
