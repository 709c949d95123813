"""The benchmark's manifest, and everything it names, found by name.

``BENCHMARK.json`` at the root of the checkout lists the cells. A cell
names its configuration (``configs/<name>.json``) and its traffic mix
(``traffic/<name>.json``); a configuration's ``kind`` names its driver
(``drivers/<kind>.py``) and its ``reference`` the plain reference
(``refs/<name>.py``); every metric, end to end or per layer, is a reader
of its own in ``metrics/<name>.py``, whose unit, layer and ``moves`` only
``BENCHMARK.json`` states. Adding a cell, a mix or a metric
therefore adds files and manifest entries and edits none.

Whatever is particular to an architecture lives in its reference
module, so that a configuration of another architecture comes in with a
module of its own. A ``kind: lm`` reference gives, for a configuration
file's contents ``c``:

- ``make_weights(seed, c)``: every weight from the seed, on the device;
- ``program_tree(weights) -> (params, specs)``: the same arrays in the
  program's dense layout;
- ``served_gaps(weights, c, prompt, served, pad_to, control=None)``: the
  normalized gap of each served token against the float32 reference;
- ``program_fields(c) -> dict``: each ``ModelConfig`` attribute that the
  file fixes, with its value; ``drivers/lm.py`` refuses a program config
  that differs in any of them;
- ``decode_linears(c) -> [(name, K, N, count)]``: every linear of one
  token's forward pass, named as the program names its kernels;
- ``decode_attention(c, ctx) -> (ops, bytes)``: one row's decode
  attention over ``ctx`` valid positions, all layers.

The last two may be left out: the readers that count decode work from
them (``decode_mfu_pct``, ``bitserial_matmul_roofline.*``) then read
None in that configuration's cells, and it may bring readers of its own.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark's directory, by path (a
    metric's name may hold a dot)."""
    key = f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}"
    if key not in sys.modules:
        path = os.path.join(HERE, kind, f"{name}.py")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    traffic: dict           # the traffic file's contents
    end_to_end: list        # manifest entries of the cell's e2e metrics
    per_layer: list         # manifest entries of the cell's per-layer ones


def _applies(metric: dict, cell: str) -> bool:
    """A metric with a ``workloads`` list is read in those cells; one
    without is read in every cell, and its reader returns None where it
    finds nothing to read."""
    return cell in metric.get("workloads", [cell])


def cell(name: str, manifest: dict | None = None) -> Cell:
    """Resolve a cell of the manifest to its files and metrics."""
    m = manifest if manifest is not None else load_manifest()
    found = [w for w in m["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r}; known: "
                       f"{[w['name'] for w in m['workloads']]}")
    w = found[0]
    cfg_entry = [c for c in m["configs"] if c["name"] == w["config"]][0]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    e2e = [e for e in m["end_to_end"] if _applies(e, name)]
    per_layer = [p for p in m["per_layer"] if _applies(p, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)
