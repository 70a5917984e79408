"""BENCHMARK.json and the files it names: present, well formed, and what
the harness finds by name."""

import json
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import compare, harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"] for m in BENCH["end_to_end"]}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert "setup_s" in E2E


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_workload_loads(w):
    cell = harness.load_cell(w["name"])
    assert cell.chips == w["chips"] == 1
    assert set(cell.limits) == set(compare.NAMES)
    assert cell.per_layer


def _count(tree) -> int:
    return sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_every_config_loads_with_its_parameter_count(c):
    """The program's initial weights and the reference's both hold the
    configuration's ``params``.  The program's model comes from the
    configuration alone; its hospitals here are a tiny cohort's."""
    conf = json.loads((ROOT / c["file"]).read_text())
    assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
    kind = harness.load_kind(conf)
    w = next(w for w in BENCH["workloads"] if w["config"] == c["name"])
    traffic = harness.load_cell(w["name"]).traffic
    assert _count(jax.eval_shape(
        lambda: kind.reference(conf, traffic).init(0))) == conf["params"]

    raw = kind.cohort(kind.tiny(conf), traffic, 0)
    model, _ = kind.program(conf, traffic, raw)
    assert _count(jax.eval_shape(model.init_fn,
                                 jax.random.key(0))) == conf["params"]


def test_published_parameter_counts():
    counts = {c["name"]: json.loads((ROOT / c["file"]).read_text())["params"]
              for c in BENCH["configs"]}
    assert counts["pancreas-mlp"] == 15_659_504
    assert counts["gemini-mlp"] == 166_771


def test_names_and_units_use_allowed_characters():
    names = ([c["name"] for c in BENCH["configs"]]
             + [w[k] for w in BENCH["workloads"]
                for k in ("name", "config", "traffic")]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"])
               for m in BENCH["end_to_end"] + BENCH["per_layer"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [x["name"] for x in BENCH[group]]
        assert len(seen) == len(set(seen)), group


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_metric_has_a_reader_and_moves_round_ms(m):
    assert m["moves"] == "round_ms" and m["moves"] in E2E
    assert set(m["workloads"]) <= {w["name"] for w in BENCH["workloads"]}
    assert callable(harness.load_reader(m["name"]))


def test_traffic_files_are_found_by_name():
    for w in BENCH["workloads"]:
        traffic = json.loads(
            (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        # the rest of a traffic file's keys are its configuration's kind's
        assert {"secagg", "batch_size"} <= set(traffic)
        assert isinstance(traffic["secagg"], bool)
        assert traffic["batch_size"] > 0
