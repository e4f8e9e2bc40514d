"""Find cells, configurations, traffic mixes, metric readers and peaks by name.

``BENCHMARK.json`` names everything; each name resolves to a file of its
own under ``bench/``.  Adding a cell, a configuration, a traffic mix or a
metric is adding files and entries: nothing here lists them.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
_E2E_KEYS = {"name", "unit", "better", "bound", "source", "workloads"}
_LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves",
               "workloads"}


class RegistryError(ValueError):
    """A name, unit or entry that the benchmark cannot resolve."""


def check_name(name, what: str = "name") -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise RegistryError(f"bad {what} {name!r}: 1-64 of [A-Za-z0-9_.-], "
                            f"starting with a letter, digit or _")
    return name


def check_unit(unit) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise RegistryError(f"bad unit {unit!r}: 1-16 of [A-Za-z0-9_/%.-]")
    return unit


def _check_metric(m: dict, keys: set, cells: set) -> None:
    extra = set(m) - keys
    if extra:
        raise RegistryError(f"metric {m.get('name')!r} has unknown keys "
                            f"{sorted(extra)}")
    check_name(m["name"], "metric name")
    check_unit(m["unit"])
    if m["better"] not in ("lower", "higher"):
        raise RegistryError(f"metric {m['name']!r}: better must be "
                            f"'lower' or 'higher'")
    if m["source"] not in SOURCES:
        raise RegistryError(f"metric {m['name']!r}: unknown source "
                            f"{m['source']!r}")
    for w in m.get("workloads", ()):
        if w not in cells:
            raise RegistryError(f"metric {m['name']!r} lists unknown cell "
                                f"{w!r}")


def load_benchmark(root: str = ROOT) -> dict:
    """``BENCHMARK.json``, with its names, units and references checked."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    configs = {check_name(c["name"], "config name") for c in bench["configs"]}
    cells = set()
    for w in bench["workloads"]:
        check_name(w["name"], "cell name")
        check_name(w["traffic"], "traffic name")
        if w["config"] not in configs:
            raise RegistryError(f"cell {w['name']!r} names unknown config "
                                f"{w['config']!r}")
        if w["chips"] not in (1, 4):
            raise RegistryError(f"cell {w['name']!r}: chips must be 1 or 4")
        cells.add(w["name"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        _check_metric(m, _E2E_KEYS, cells)
        if m["source"] not in ("host_clock", "device_trace"):
            raise RegistryError(f"end-to-end metric {m['name']!r} must come "
                                f"from host_clock or device_trace")
    for m in bench["per_layer"]:
        _check_metric(m, _LAYER_KEYS, cells)
        if m["moves"] not in e2e:
            raise RegistryError(f"metric {m['name']!r} moves unknown "
                                f"end-to-end metric {m['moves']!r}")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    if len(names) != len(set(names)):
        raise RegistryError("two metrics share a name")
    return bench


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise RegistryError(f"no cell named {name!r} in BENCHMARK.json")


def _json(root: str, sub: str, name: str) -> dict:
    check_name(name)
    path = os.path.join(root, "bench", sub, name + ".json")
    if not os.path.isfile(path):
        raise RegistryError(f"no file {os.path.relpath(path, root)}")
    with open(path) as f:
        return json.load(f)


def load_config(name: str, root: str = ROOT) -> dict:
    return _json(root, "configs", name)


def load_traffic(name: str, root: str = ROOT) -> dict:
    return _json(root, "workloads", name)


def _module(root: str, sub: str, name: str):
    check_name(name)
    path = os.path.join(root, "bench", sub, name + ".py")
    if not os.path.isfile(path):
        raise RegistryError(f"no file {os.path.relpath(path, root)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{sub}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: str = ROOT):
    """The metric reader ``bench/metrics/<name>.py`` (has ``read(run)``)."""
    mod = _module(root, "metrics", name)
    if not callable(getattr(mod, "read", None)):
        raise RegistryError(f"bench/metrics/{name}.py defines no read(run)")
    return mod


def load_system(name: str, root: str = ROOT):
    """The driver ``bench/systems/<name>.py`` (has ``System``)."""
    return _module(root, "systems", name)


def load_reference(name: str, root: str = ROOT):
    """The plain reference ``bench/reference/<name>.py``."""
    return _module(root, "reference", name)


def load_control(system: str, name: str, root: str = ROOT):
    """The control ``bench/controls/<system>.<name>.py``: a run of any
    cell on that system with one stated guarantee broken.  It has
    ``FAILS`` (the compared number it must fail) and a ``fault(system)``,
    ``CONFIG_OVERRIDES`` or both."""
    check_name(name, "control name")
    mod = _module(root, "controls", f"{system}.{name}")
    if not isinstance(getattr(mod, "FAILS", None), str) or not (
            callable(getattr(mod, "fault", None))
            or isinstance(getattr(mod, "CONFIG_OVERRIDES", None), dict)):
        raise RegistryError(f"bench/controls/{system}.{name}.py needs FAILS "
                            f"and a fault(system) or CONFIG_OVERRIDES")
    return mod


def controls_for(system: str, root: str = ROOT) -> list:
    """Names of the controls ``bench/controls/`` holds for ``system``."""
    d = os.path.join(root, "bench", "controls")
    pre = system + "."
    return sorted(f[len(pre):-3] for f in os.listdir(d)
                  if f.startswith(pre) and f.endswith(".py"))


def metrics_for(bench: dict, cell_name: str, per_layer: bool) -> list:
    """The metrics a run of ``cell_name`` reports: its end-to-end metrics
    with ``--trace 0``, its per-layer metrics with ``--trace 1``.

    A metric without ``workloads`` belongs to every cell; a per-layer
    metric without it, to every cell that reports the metric it moves."""
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not per_layer:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and m["moves"] in mine]


def load_peaks(device_kind: str, root: str = ROOT) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown is an error."""
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)
    for entry in table["devices"]:
        if device_kind in entry["device_kinds"]:
            return entry
    raise RegistryError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json")
