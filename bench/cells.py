"""A cell of ``BENCHMARK.json`` and the files it is made of, found by
name: its configuration (the entry's ``file``), the configuration's
plain reference (``reference/<name>.py``, named by the configuration),
its traffic mix (``traffic/<mix>.json``) and a reader per metric
(``metrics/<name>.py``).

Each lookup tries the benchmark root's own ``bench`` directory first and
then this one, so a cell whose files live beside another
``BENCHMARK.json`` runs with this harness unchanged.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")


def _checked(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"{name!r} is not a benchmark name")
    return name


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: object    # read(ctx) -> float | None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    reference: object   # the reference module: replay(cluster, trace)
    spec: dict      # the traffic file, with the configuration's job size
    end_to_end: list
    per_layer: list


def _find(root: Path, rel: str) -> Path:
    for base in dict.fromkeys((root / "bench", HERE)):
        p = base / rel
        if p.is_file():
            return p
    raise FileNotFoundError(f"no {rel} under {root / 'bench'} or {HERE}")


def _module(root: Path, kind: str, name: str):
    """The module ``<kind>/<name>.py``, loaded from its file."""
    path = _find(root, f"{kind}/{_checked(name)}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _metrics(root: Path, entries: list, cell: str) -> list:
    return [Metric(m["name"], m["unit"],
                   _module(root, "metrics", m["name"]).read)
            for m in entries if cell in m.get("workloads", [cell])]


def merge(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, dicts merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = (merge(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def load(root: Path, workload: str, rehearsal: bool = False) -> Cell:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``.  With
    ``rehearsal`` the traffic file's ``rehearsal`` overrides shrink it to
    a size the CPU runs in seconds."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise KeyError(f"no workload {workload!r} in {root}/BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(_find(
        root, f"traffic/{_checked(cell['traffic'])}.json").read_text())
    spec = {"job_events": config["job_events"],
            **{k: v for k, v in traffic.items() if k != "rehearsal"}}
    if rehearsal:
        spec = merge(spec, traffic.get("rehearsal", {}))
    return Cell(name=workload, chips=int(cell["chips"]), config=config,
                reference=_module(root, "reference", config["reference"]),
                spec=spec,
                end_to_end=_metrics(root, bench["end_to_end"], workload),
                per_layer=_metrics(root, bench["per_layer"], workload))
