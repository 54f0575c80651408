"""BENCHMARK.json and the files it names keep to the benchmark's rules,
and the peaks table and the rmq kernel's cost function read as stated."""
import json
import re
from pathlib import Path

import pytest

from bench import harness
from bench.kernels import rmq
from bench.peaks import peaks

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_lengths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    cells = 2 + 14 * 24
    assert cells * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_every_cell_finds_its_files_by_name():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        cell = harness.load_cell(w["name"])
        assert cell.config["reduced"] == []
        assert {"generator", "parse", "serving", "k"} <= set(cell.config)
        assert cell.traffic["request_rate"] > 0
        assert cell.per_layer, w["name"]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert callable(harness.load_reader(m["name"]))


@pytest.mark.parametrize("config,traffic", [("qac-aol", "first-term"),
                                            ("qac-ebay", "sessions")])
def test_sweep_finds_a_draft_cell_by_name(config, traffic):
    cell = harness.draft_cell(config, traffic)
    assert cell.config["name"] == config and cell.chips == 1
    assert cell.traffic["request_rate"] > 0
    harness.traffic_params(cell.traffic)


def test_peaks_table_refuses_an_unknown_device():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("cpu")


def test_rmq_cost_reads_the_call_shape():
    op = type("Op", (), {"name": (
        "%rmq_query.17 = s32[512,2]{1,0:T(8,128)S(1)} custom-call(s32[512,2]"
        "{1,0:T(8,128)S(1)} %add_select_fusion.6, s32[512,128]{1,0} %f)")})
    assert rmq.matches(op) and rmq.lanes(op) == 512
    ops, nbytes = rmq.cost(op)
    assert nbytes == 512 * 264 * 4
    assert ops == 512 * (8 * 2 * 128 + 24)
    other = type("Op", (), {"name": (
        "%copy-start = (s32[4,6]{1,0}) copy-start(s32[4,6]{1,0} "
        "%qidx_rmq_minimal_st_pos.1)")})
    assert not rmq.matches(other)
