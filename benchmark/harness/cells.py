"""Find a cell's files by the names in BENCHMARK.json. A later PR adds a
cell as one `workloads` entry plus files; nothing here names a cell."""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)


def place_caches() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the key), and no libtpu logs under /tmp.
    Call before JAX is imported: the program honours the variable and so
    takes this directory."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                         f"{sorted(by_name)}")
    work = by_name[name]
    cfg = {c["name"]: c for c in bench["configs"]}[work["config"]]
    config = _read_json(os.path.join(root, cfg["file"]))
    traffic = _read_json(os.path.join(BENCH_DIR, "traffic",
                                      work["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _in_cell(m, name)]
    rates = {m["name"] for m in e2e}
    layers = [m for m in bench["per_layer"]
              if _in_cell(m, name) and m["moves"] in rates]
    return Cell(name=name, chips=int(work["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layers)


def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py` as a module (names may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
