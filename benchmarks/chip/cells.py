"""Find a cell's files by name.

A cell ``<name>`` is ``workloads/<name>.json``: the configuration and the
traffic it pairs, the chips it needs and the limits of its correctness
comparison.  The configuration is ``configs/<config>.json``, the traffic
``traffic/<traffic>.json`` and each per-layer metric
``metrics/<metric>.py``.  Adding a cell, a configuration, a traffic mix
or a metric means adding a file here; nothing is listed in code.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _json(kind: str, name: str) -> dict:
    if not _NAME.match(name):
        raise ValueError(f"not a {kind} name: {name!r}")
    path = os.path.join(HERE, kind, name + ".json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    with open(path) as f:
        return json.load(f)


def load(name: str) -> dict:
    """The cell ``name`` with its configuration and traffic resolved:
    ``{"name", "chips", "limits", "config": {...}, "traffic": {...}}``."""
    cell = _json("workloads", name)
    cell = dict(cell, name=name,
                config=dict(_json("configs", cell["config"]),
                            name=cell["config"]),
                traffic=dict(_json("traffic", cell["traffic"]),
                             name=cell["traffic"]))
    t = cell["traffic"]
    if t["chips"] != cell["chips"]:
        raise ValueError(f"{name}: the cell asks for {cell['chips']} chips, "
                         f"its traffic {t['name']} for {t['chips']}")
    workers = 1
    for g in t["levels"]:
        workers *= g
    if workers != t["workers"]:
        raise ValueError(f"{name}: levels {t['levels']} do not make "
                         f"{t['workers']} workers")
    if cell["chips"] not in (1, 4):
        raise ValueError(f"{name}: chips must be 1 or 4")
    return cell


def names(kind: str) -> list[str]:
    """Every name of one kind of file (``workloads``, ``configs``,
    ``traffic`` or ``metrics``) that exists."""
    ext = ".py" if kind == "metrics" else ".json"
    d = os.path.join(HERE, kind)
    return sorted(f[:-len(ext)] for f in os.listdir(d)
                  if f.endswith(ext) and not f.startswith("_"))


def metric_reader(name: str):
    """``(read, unit, kind)`` of ``metrics/<name>.py``: its ``read(ctx)``
    function, its ``UNIT`` and its ``KIND`` (``end_to_end`` or
    ``per_layer``)."""
    if not _NAME.match(name):
        raise ValueError(f"not a metric name: {name!r}")
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + re.sub(r"\W", "_", name), path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(f"no metric reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read, mod.UNIT, mod.KIND
