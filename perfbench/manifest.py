"""BENCHMARK.json and the data files it names. Imports no JAX.

A cell is found by name; its configuration, traffic mix, driver and
per-layer metrics are files found by the names the manifest gives, so
a later PR adds a cell as new files plus new entries and edits nothing
that is here.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: What the driver accepts as a name (metric, cell, config, traffic).
NAME_RE = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT_RE = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')


class ManifestError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _load_json(path: str) -> Any:
    try:
        with open(path, 'r', encoding='utf-8') as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f'{path}: {e}') from None


def load(path: str = os.path.join(ROOT, 'BENCHMARK.json')
         ) -> Dict[str, Any]:
    return _load_json(path)


def cell(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in manifest['workloads']:
        if w['name'] == name:
            return w
    raise ManifestError(
        f'no workload {name!r} in BENCHMARK.json (it has '
        f'{[w["name"] for w in manifest["workloads"]]})')


def config(manifest: Dict[str, Any], name: str,
           root: str = ROOT) -> Dict[str, Any]:
    for c in manifest['configs']:
        if c['name'] == name:
            return _load_json(os.path.join(root, c['file']))
    raise ManifestError(f'no config {name!r} in BENCHMARK.json')


def mix(traffic: str) -> Dict[str, Any]:
    return _load_json(os.path.join(HERE, 'mixes', f'{traffic}.json'))


def _in_cell(metric: Dict[str, Any], cell_name: str) -> bool:
    return 'workloads' not in metric or cell_name in metric['workloads']


def end_to_end(manifest: Dict[str, Any],
               cell_name: str) -> List[Dict[str, Any]]:
    return [m for m in manifest['end_to_end'] if _in_cell(m, cell_name)]


def per_layer(manifest: Dict[str, Any],
              cell_name: str) -> List[Dict[str, Any]]:
    """The cell's per-layer metrics, each with its reader spec from
    `layer_metrics/<name>.json` under the key `spec`."""
    out = []
    for m in manifest['per_layer']:
        if _in_cell(m, cell_name):
            spec = _load_json(os.path.join(HERE, 'layer_metrics',
                                           f'{m["name"]}.json'))
            out.append(dict(m, spec=spec))
    return out


def _module(kind: str, name: str):
    if not NAME_RE.match(name):
        raise ManifestError(f'bad {kind} name {name!r}')
    path = os.path.join(HERE, kind, f'{name}.py')
    if not os.path.isfile(path):
        raise ManifestError(f'no {kind} file {path}')
    spec = importlib.util.spec_from_file_location(
        f'perfbench.{kind}.{name}', path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str):
    """`perfbench/drivers/<name>.py`; it has `run(ctx)`."""
    return _module('drivers', name)


def reference(name: str):
    """`perfbench/references/<name>.py`: a configuration's plain
    reference; it has `log_probs(params, config, tokens)`."""
    return _module('references', name)


def sizes(family: str):
    """`perfbench/sizes/<family>.py`: `params(cfg)`,
    `train_flops_per_token(cfg, seq)`, `serve_flops_per_token(cfg)`."""
    return _module('sizes', family)


def roofline(name: str):
    """`perfbench/rooflines/<name>.py`; it has `cost(sources)` ->
    `{'flops', 'bytes'}` over the traced span, or None."""
    return _module('rooflines', name)


def reader(name: str):
    """`perfbench/readers/<name>.py`; it has `read(sources, **args)`."""
    return _module('readers', name)
