"""The benchmark's description, and the files it names.

``BENCHMARK.json`` at the checkout's root lists the cells, configurations
and metrics.  Everything that belongs to one of them sits in a file of its
own, found by name:

* ``bench/configs/<config>.json``   the configuration as run (``file`` key)
* ``bench/traffic/<traffic>.json``  the traffic mix's parameters
* ``bench/limits/<cell>.json``      the limits ``correct`` is decided by
* ``bench/metrics/<metric>.py``     the reader of one metric
* ``bench/families/<family>.py``    a model family (a configuration's
  ``program.family``): its sizes, the program's config, its FLOP count
  and the plain reference's layer

so a later cell, mix, metric or family adds files and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Any, Dict, List

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class SpecError(RuntimeError):
    """The benchmark's description does not name what was asked for."""


def load_json(path: str) -> Any:
    with open(path) as fh:
        return json.load(fh)


def load_benchmark() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _named(entries: List[Dict[str, Any]], name: str, what: str
           ) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"BENCHMARK.json names no {what} {name!r}")


def cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    return _named(bench["workloads"], name, "workload")


def config_entry(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    return _named(bench["configs"], name, "configuration")


def config_file(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, config_entry(bench, name)["file"]))


def traffic_file(name: str) -> Dict[str, Any]:
    return load_json(os.path.join(BENCH, "traffic", f"{name}.json"))


def limits_file(cell_name: str) -> Dict[str, Any]:
    return load_json(os.path.join(BENCH, "limits", f"{cell_name}.json"))


def metrics_for(bench: Dict[str, Any], cell_name: str, kind: str
                ) -> List[Dict[str, Any]]:
    """The ``kind`` ("end_to_end" or "per_layer") metrics the cell reports:
    those whose ``workloads`` list it, or that have no such list."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def _load(path: str, prefix: str, name: str) -> ModuleType:
    mod_name = prefix + "".join(c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod      # where dataclasses look up its names
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> ModuleType:
    """``bench/metrics/<name>.py``, which defines ``read(run)``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise SpecError(f"no reader {path} for metric {name!r}")
    return _load(path, "bench_metric_", name)


def family(name: str) -> ModuleType:
    """``bench/families/<name>.py``, which defines ``Sizes``,
    ``program_config(name, cfg_file, smoke)``, ``sizes(cfg_file, smoke)``,
    ``request_flops(sizes, processed, history, n)``, ``Weights(sizes)``
    (``embed()``, ``head()``, ``layer(i)``) and ``layer(x, weights, sizes,
    quant)``."""
    path = os.path.join(BENCH, "families", f"{name}.py")
    if not os.path.exists(path):
        raise SpecError(f"no family {path} for family {name!r}")
    return _load(path, "bench_family_", name)


def config_family(bench: Dict[str, Any], name: str) -> ModuleType:
    """The family configuration ``name``'s file names under
    ``program.family``."""
    fam = config_file(bench, name).get("program", {}).get("family")
    if fam is None:
        path = config_entry(bench, name)["file"]
        raise SpecError(f"{path} names no program.family")
    return family(fam)
