"""Find a cell's pieces by name: its configuration, its traffic mix, its
metrics and the modules that read them.

Every piece lives in a file of its own, named after the entry in
``BENCHMARK.json``, so that a new configuration, mix or metric is a new
file and never an edit:

* ``bench/configs/<config>.json``   sizes, widths and slots as run;
* ``bench/traffic/<traffic>.json``  parameters of a mix, whose ``kind``
  names the generator ``bench/generators/<kind>.py``;
* ``bench/metrics/<metric>.py``     a ``read(run)`` for every metric;
* ``bench/work/<work>.py``          operations and bytes of a step, named
  by the configuration's ``work`` key;
* ``bench/reference/<reference>.py`` the plain reference, named by the
  configuration's ``reference`` key.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
import sys
from typing import Any, Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")


def check_name(name: str) -> str:
    """A name as the manifest allows it; refuse anything else before it
    becomes part of a path."""
    if not isinstance(name, str) or not _NAME.fullmatch(name):
        raise ValueError(f"not a valid benchmark name: {name!r}")
    return name


def load_manifest(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _json(kind: str, name: str, bench: pathlib.Path) -> Dict[str, Any]:
    path = bench / kind / f"{check_name(name)}.json"
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench: pathlib.Path = BENCH):
    """Import ``bench/<kind>/<name>.py`` by path (names may hold dots);
    each file is imported once."""
    path = (bench / kind / f"{check_name(name)}.py").resolve()
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module for {name!r}: {path}")
    mod_name = "bench_" + re.sub(r"[^A-Za-z0-9_]", "_", str(path))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of the manifest with everything it names, resolved."""

    name: str
    entry: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench: pathlib.Path = BENCH

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    def metrics(self, trace: bool) -> List[Dict[str, Any]]:
        return self.per_layer if trace else self.end_to_end


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell in cells


def resolve(name: str, manifest: Optional[Dict[str, Any]] = None,
            bench: pathlib.Path = BENCH) -> Cell:
    manifest = load_manifest(bench.parent) if manifest is None else manifest
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if not entries:
        known = sorted(w["name"] for w in manifest["workloads"])
        raise KeyError(f"unknown workload {name!r}; known: {known}")
    entry = entries[0]
    return Cell(
        name=name, entry=entry,
        config=_json("configs", entry["config"], bench),
        traffic=_json("traffic", entry["traffic"], bench),
        end_to_end=[m for m in manifest["end_to_end"]
                    if _applies(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _applies(m, name)],
        bench=bench,
    )


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file: the named
    architecture with the file's overrides applied, checked against the
    sizes the file states (which the reference reads)."""
    from repro.configs import get_config

    cfg = get_config(conf["arch"])
    top, comp = {}, {}
    for key, val in conf.get("overrides", {}).items():
        if key.startswith("compression."):
            comp[key.split(".", 1)[1]] = val
        else:
            top[key] = val
    cfg = dataclasses.replace(
        cfg, **top,
        compression=dataclasses.replace(cfg.compression, **comp))
    for key, want in conf["model"].items():
        if key == "rms_norm_eps":
            continue
        have = (cfg.resolved_head_dim if key == "head_dim"
                else getattr(cfg, key))
        if have != want:
            raise ValueError(
                f"{conf['name']}: program config has {key}={have!r}, "
                f"the file states {want!r}")
    if cfg.compression.weight_bits != conf["weight_bits"] or \
            cfg.compression.kv_bits != conf["kv_bits"]:
        raise ValueError(f"{conf['name']}: widths differ from the file")
    return cfg
