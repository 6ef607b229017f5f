"""``BENCHMARK.json`` against the rules the harness relies on: names and
units, every cell resolving its configuration, mix and readers, every
per-layer metric's ``moves`` reported where it is read, and the check's
time budget."""
import json
import math
import re

import pytest

from bench import cell as cells

MAN = cells.load_manifest()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
CELLS = [w["name"] for w in MAN["workloads"]]
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["paths"] == ["bench"]
    assert MAN["command"][1] == "bench/run.py"
    assert isinstance(MAN["run_seconds"], int)


def test_names_and_units():
    names = ([c["name"] for c in MAN["configs"]] + CELLS
             + [m["name"] for m in METRICS]
             + [w["traffic"] for w in MAN["workloads"]]
             + [k for c in MAN["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.fullmatch(n), n
    for group in (MAN["configs"], MAN["workloads"], METRICS):
        assert len({x["name"] for x in group}) == len(group)
    for m in METRICS:
        assert UNIT.fullmatch(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in MAN["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = cells.resolve(name)
    conf = cell.config
    entry = next(c for c in MAN["configs"] if c["name"] == conf["name"])
    assert entry["file"] == f"bench/configs/{conf['name']}.json"
    assert entry["reduced"] == conf["reduced"]
    cells.model_config(conf)          # sizes agree with the program's
    cells.load_module("generators", cell.traffic["kind"])
    cells.load_module("work", conf["work"])
    cells.load_module("reference", conf["reference"])
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cells.load_module("metrics", m["name"]).read)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


def test_every_config_is_used():
    used = {w["config"] for w in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}


def test_layers_named_alike():
    for m in MAN["per_layer"]:
        assert m["layer"] and "\n" not in m["layer"]
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_run_seconds_fits_full_check():
    """A full check of 24 cells must fit: 2 + 14 runs a cell, each
    ``run_seconds`` + 60 s, 2 x 90 s of compiling a cell, 1200 s spare."""
    r = MAN["run_seconds"]
    assert 1 <= r <= 51
    assert (2 + 14 * 24) * (r + 60) + 24 * 180 + 1200 <= 43200


def test_reduced_keys_name_no_width_and_differ_from_source():
    widths = re.compile(r"(_dim|_rank)$|hidden|intermediate|d_model|d_ff"
                        r"|heads|experts_per_token")
    for c in MAN["configs"]:
        conf = json.loads(open(cells.ROOT / c["file"]).read())
        for k in c["reduced"]:
            assert not widths.search(k), k
            assert k in conf["published"] and \
                conf["published"][k] != conf["model"][k]
        assert math.isfinite(conf["check"]["logit_gap"])
