"""Finds a cell's pieces by name.  ``BENCHMARK.json`` at the root of the
checkout names the cell, its configuration file and its metrics; the
traffic mix of a cell is ``bench/traffic/<traffic>.json``, each metric
is read by ``bench/metrics/<name>.py``, and a configuration's
architecture is ``bench/archs/<name>.py``, the name given under
``program.bench_arch`` in the configuration file (``dense`` where it is
absent).  A later change adds a configuration, an architecture, a mix or
a metric by adding such files and entries, and edits none.

A metric file defines ``read(run) -> float | None``; ``run`` is the
harness's record of one run (see ``harness.Run``).  A reader that finds
nothing to read returns None and the metric is left out of the line.

An architecture module defines, and is the only part of the benchmark
that knows an architecture's keys and layers:

  program_config(config) -> ModelConfig    the program's model for it
  shapes(m, padded_vocab) -> {path: shape} the weight tree's leaves
  leaf(path, shape, m) -> (kind, fan_in, dtype)
                                           each leaf's fill, fan-in and
                                           dtype (``bench/weights.py``)
  hidden(w, tokens, m, control)            the reference's final-norm
                                           hidden states (T, d)
  layer_windows(m) -> [int | None]         per layer, its attention
                                           window, None for full
  layer_params(m) -> float                 the matrix parameters each
                                           token passes through here
"""
from __future__ import annotations

import importlib.util
import json
import os


class UnknownName(KeyError):
    pass


def _by_name(entries, name: str, kind: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise UnknownName(f"no {kind} named {name!r} in BENCHMARK.json "
                      f"(have {sorted(e['name'] for e in entries)})")


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(bench: dict, workload: str, root: str) -> dict:
    """Everything a run of ``workload`` needs: the workload entry, its
    configuration (the file, parsed) and architecture module, the
    traffic file's path and the metrics it reports with and without a
    trace."""
    w = _by_name(bench["workloads"], workload, "workload")
    c = _by_name(bench["configs"], w["config"], "config")
    with open(os.path.join(root, c["file"])) as f:
        config = json.load(f)
    mix = os.path.join(root, "bench", "traffic", w["traffic"] + ".json")
    if not os.path.isfile(mix):
        raise UnknownName(f"no traffic file {mix}")

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return {"workload": w, "config": config, "config_name": c["name"],
            "arch": arch(root, config.get("program", {}).get("bench_arch",
                                                             "dense")),
            "traffic": mix, "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


_modules: dict = {}


def _module(path: str, prefix: str, name: str):
    """The module in file ``path``, loaded once per process (so the
    functions an architecture jits keep their compiled programs)."""
    if path not in _modules:
        spec = importlib.util.spec_from_file_location(
            prefix + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[path] = mod
    return _modules[path]


def reader(root: str, name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    if not os.path.isfile(path):
        raise UnknownName(f"no reader {path} for metric {name!r}")
    return _module(path, "bench_metric_", name).read


def arch(root: str, name: str):
    """The architecture module ``bench/archs/<name>.py``."""
    path = os.path.join(root, "bench", "archs", name + ".py")
    if not os.path.isfile(path):
        raise UnknownName(f"no architecture module {path} named {name!r}")
    return _module(path, "bench_arch_", name)


def peak(root: str, kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise UnknownName(f"no peaks for device kind {kind!r} in "
                          f"bench/peaks.json (have {sorted(table)})")
    return table[kind]
