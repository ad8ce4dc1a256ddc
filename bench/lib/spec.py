"""Find a cell's pieces by the names BENCHMARK.json gives them.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, looked up by name, so adding a cell means
adding files and entries and editing none:

    <file of the config entry>          the configuration (JSON)
    bench/traffic/<traffic>.json        the traffic mix; its "driver" key
                                        names the generator that reads it
    bench/drivers/<driver>.py           the generator (one per kind)
    bench/metrics/<metric>.py           one reader per per-layer metric
    bench/references/<reference>.py     the plain reference a config names
    bench/limits/<workload>.json        the limits of the numbers the cell
                                        compares with the reference
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from types import ModuleType
from typing import Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[2]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    driver: ModuleType
    reference: ModuleType
    end_to_end: List[Dict]
    per_layer: List[Dict]
    readers: Dict[str, ModuleType]
    root: pathlib.Path

    def limits(self) -> Dict[str, float]:
        """Limits of the numbers this cell's driver compares, from the
        cell's own limits file."""
        path = self.root / "bench" / "limits" / f"{self.name}.json"
        return {k: float(v) for k, v in json.loads(path.read_text()).items()}


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a file by path; its module name is derived from the path so
    names with dots (device_idle_pct.fit) load like any other."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = "bench_plugin_" + "_".join(path.with_suffix("").parts[-2:]) \
        .replace(".", "_").replace("-", "_")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod
    mod_spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: pathlib.Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _by_name(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json; have "
                   f"{[e['name'] for e in entries]}")


def _reports(metric: Dict, cell: str, e2e_names: Optional[set]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def resolve(cell_name: str, root: pathlib.Path = ROOT,
            bench: Optional[Dict] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    wl = _by_name(bench["workloads"], cell_name, "workload")
    cfg_entry = _by_name(bench["configs"], wl["config"], "config")
    config = json.loads((root / cfg_entry["file"]).read_text())
    bench_dir = root / "bench"
    traffic = json.loads((bench_dir / "traffic" /
                          f"{wl['traffic']}.json").read_text())
    driver = load_module(bench_dir / "drivers" / f"{traffic['driver']}.py")
    reference = load_module(bench_dir / "references" /
                            f"{config['reference']}.py")
    e2e = [m for m in bench["end_to_end"] if _reports(m, cell_name, None)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, cell_name, e2e_names)]
    readers = {m["name"]: load_module(bench_dir / "metrics" /
                                      f"{m['name']}.py")
               for m in per_layer}
    return Cell(name=cell_name, chips=int(wl["chips"]), config=config,
                traffic=traffic, driver=driver, reference=reference,
                end_to_end=e2e, per_layer=per_layer, readers=readers,
                root=root)
