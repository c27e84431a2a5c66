"""BENCHMARK.json against the benchmark contract, and every file a cell is
found by."""
import json
import os
import re

import pytest

from bench import cell as cells
from bench.tests.tiny import REPO

with open(os.path.join(REPO, "BENCHMARK.json")) as _fh:
    MAN = json.load(_fh)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
#: keys a cut may change: scale, never a width
CUTTABLE = {"num_layers"}


def test_keys_and_command():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "bench/run.py"]
    assert MAN["paths"] == ["bench"]
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51


def test_names_and_units():
    metrics = MAN["end_to_end"] + MAN["per_layer"]
    names = ([c["name"] for c in MAN["configs"]]
             + [w["name"] for w in MAN["workloads"]]
             + [m["name"] for m in metrics]
             + [w["traffic"] for w in MAN["workloads"]]
             + [k for c in MAN["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    for group in (MAN["configs"], MAN["workloads"], metrics):
        assert len({x["name"] for x in group}) == len(group)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert {"setup_s", "tokens_per_s", "mfu", "step_s.p90",
            "peak_hbm_gb"} <= set(e2e)
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_per_layer_metrics_have_readers():
    e2e = {m["name"] for m in MAN["end_to_end"]}
    cellnames = {w["name"] for w in MAN["workloads"]}
    for m in MAN["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert set(m.get("workloads", cellnames)) <= cellnames
        assert os.path.isfile(os.path.join(REPO, "bench", "layers",
                                           m["name"] + ".py"))


@pytest.mark.parametrize("w", MAN["workloads"], ids=lambda w: w["name"])
def test_cell_files(w):
    c = cells.load(os.path.join(REPO, "BENCHMARK.json"), w["name"])
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    fam = c.config["family"]
    for kind in ("reference", "flops"):
        assert os.path.isfile(os.path.join(REPO, "bench", kind, fam + ".py"))
    names = {"grad1_gap", "change_gap", "mom_gap"}
    assert c.limits["numbers"] and set(c.limits["numbers"]) <= (
        names | {n + ".median" for n in names})
    assert c.metrics["end_to_end"] and c.metrics["per_layer"]


@pytest.mark.parametrize("conf", MAN["configs"], ids=lambda c: c["name"])
def test_configs_cut_no_width_and_match_the_program(conf):
    from repro.configs import ARCHS
    assert set(conf["reduced"]) <= CUTTABLE
    assert conf["file"].startswith("bench/")
    assert any(w["config"] == conf["name"] for w in MAN["workloads"])
    with open(os.path.join(REPO, conf["file"])) as fh:
        config = json.load(fh)
    assert sorted(config["reduced"]) == sorted(conf["reduced"])
    before = dict(ARCHS)
    with cells.registered(config) as name:
        cut = ARCHS[name]
        for k, v in config["sizes"].items():
            assert getattr(cut, k) == v
    assert ARCHS == before


def test_registered_leaves_archs_as_found_after_an_error():
    from repro.configs import ARCHS
    from bench.tests.tiny import SSM
    before = dict(ARCHS)
    with pytest.raises(RuntimeError):
        with cells.registered(SSM):
            assert "tiny-ssm" in ARCHS
            raise RuntimeError
    assert ARCHS == before


def test_registered_refuses_a_size_the_program_does_not_have():
    from bench.tests.tiny import SSM
    bad = dict(SSM, reduced=[k for k in SSM["reduced"] if k != "d_model"])
    with pytest.raises(SystemExit):
        with cells.registered(bad):
            pass
