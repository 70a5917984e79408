"""The per-layer readers of the program's SecAgg, fetch, update and
accountant spans, on hand-built run records: each reads the mean of its
span per round, ``secagg.transfer_mb`` sums the spans' bytes per round, and
every one reads nothing where its spans are absent (a program without
them, or a cell with SecAgg off)."""

import pytest

from bench import harness

MEAN_READERS = {
    "payload_fetch_ms": "fused_round.fetch",
    "secagg.encode_ms": "secagg.encode",
    "secagg.masks_ms": "secagg.masks",
    "secagg.sum_ms": "secagg.sum",
    "update_ms": "update",
    "account_ms": "account",
}


def span(name, dur, depth=1, **args):
    return {"type": "span", "name": name, "ts": 0.0, "dur": dur,
            "depth": depth, "args": args}


def record(spans):
    return harness.RunRecord(config={}, traffic={}, window_s=1.0, logs=[],
                             spans=spans, trace=None, peak=None)


@pytest.mark.parametrize("metric", sorted(MEAN_READERS))
def test_mean_span_milliseconds_per_round(metric):
    name = MEAN_READERS[metric]
    spans = [span("round", 1.0, depth=0), span(name, 0.010),
             span("round", 1.0, depth=0), span(name, 0.030),
             span("aggregate", 0.5)]
    assert harness.load_reader(metric)(record(spans)) == pytest.approx(20.0)


@pytest.mark.parametrize("metric", sorted(MEAN_READERS)
                         + ["secagg.transfer_mb"])
def test_absent_spans_read_nothing(metric):
    """What a program without these spans records: rounds, the fused step,
    the host draw and the aggregate."""
    spans = [span("round", 1.0, depth=0), span("fused_round", 0.2),
             span("host_rng.stack_poisson", 0.1, depth=2, cohort=5, pad=128),
             span("aggregate", 0.5)]
    assert harness.load_reader(metric)(record(spans)) is None


@pytest.mark.parametrize("metric", [m for m in MEAN_READERS
                                    if m.startswith("secagg.")])
def test_secagg_readers_read_nothing_with_secagg_off(metric):
    spans = [span("round", 1.0, depth=0), span("fused_round", 0.2),
             span("fused_round.fetch", 0.1, depth=2, bytes=20),
             span("aggregate", 0.01), span("update", 0.01, depth=2),
             span("account", 0.001)]
    assert harness.load_reader(metric)(record(spans)) is None


def test_transfer_mb_sums_the_bytes_of_a_round():
    """Payloads and losses up, net masks up, the sum down: per round."""
    n, length, f32 = 5, 15_659_504, 4
    one_round = [span("round", 9.0, depth=0),
                 span("fused_round.fetch", 0.2, depth=2,
                      bytes=n * length * f32 + n * f32),
                 span("secagg.masks", 0.3, depth=3, pairs=10,
                      bytes=n * length * f32),
                 span("secagg.sum", 2.0, depth=2, bytes=length * f32),
                 span("secagg.upload", 6.0, depth=2, participants=n,
                      length=length)]
    read = harness.load_reader("secagg.transfer_mb")
    assert read(record(one_round)) == pytest.approx(689.018196, abs=1e-9)
    assert read(record(one_round * 3)) == pytest.approx(689.018196, abs=1e-9)
