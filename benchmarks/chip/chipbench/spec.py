"""Finds a cell's pieces by name: ``BENCHMARK.json`` at the checkout's
root names the cell's configuration and traffic mix; the configuration is
the file its entry names, the mix is ``traffic/<mix>.json``, a metric is
``metrics/<metric>.py`` and the device's peaks are in ``peaks.json``.
Adding a cell or a metric adds files and entries; nothing here changes."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parents[1]        # benchmarks/chip
ROOT = HERE.parents[1]                            # the checkout


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_traffic(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def cell(name: str, bench: Optional[dict] = None, root: Path = ROOT) -> dict:
    """Everything one workload needs: its BENCHMARK.json entry, the
    configuration and traffic mix as loaded from their files, and the
    names of the metrics it reports with the profiler off and on."""
    bench = bench or load_benchmark(root)
    wl = _by_name(bench["workloads"], name, "workload")
    cfg_entry = _by_name(bench["configs"], wl["config"], "config")
    config = json.loads((root / cfg_entry["file"]).read_text())

    def reported(metrics: List[dict]) -> List[str]:
        return [m["name"] for m in metrics
                if name in m.get("workloads", [name])]

    return {"workload": wl, "config": config,
            "traffic": load_traffic(wl["traffic"]),
            "end_to_end": reported(bench["end_to_end"]),
            "per_layer": reported(bench["per_layer"]),
            "units": {m["name"]: m["unit"]
                      for m in bench["end_to_end"] + bench["per_layer"]}}


def metric(name: str) -> Callable[[dict], Optional[float]]:
    """``compute(record)`` of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.compute


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; a kind missing from
    ``peaks.json`` is an error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]
