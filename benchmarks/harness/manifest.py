"""``BENCHMARK.json`` and the files it names. Standard library only.

Whatever belongs to one configuration, one traffic mix or one metric is
a file of its own that is found by the name in the manifest:

- ``benchmarks/configs/<config>.json``
- ``benchmarks/traffic/<traffic>.json``
- ``benchmarks/metrics/<metric>.json`` (and ``<metric>.py`` for a reader
  of its own)
- ``benchmarks/references/<family>.py``

so a later PR adds files and manifest entries and edits nothing here.
"""

import dataclasses
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]  # manifest entries that apply to this cell
    per_layer: tuple[dict, ...]


def manifest(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def metric_file(name: str, root: Path = ROOT) -> dict:
    """The metric's own file: unit, layer, what it is and its reader."""
    return load_json(root / BENCH_DIR.name / "metrics" / f"{name}.json")


def applies(entry: dict, cell_name: str) -> bool:
    """The manifest alone says which cells report a metric: those its
    ``workloads`` lists, or every cell when it lists none."""
    return cell_name in entry.get("workloads", [cell_name])


def cell(name: str, root: Path = ROOT) -> Cell:
    bench = manifest(root)
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json ({known})")
    entry = entries[0]
    config_entry = next(
        c for c in bench["configs"] if c["name"] == entry["config"]
    )
    config = load_json(root / config_entry["file"])
    traffic = load_json(
        root / BENCH_DIR.name / "traffic" / f"{entry['traffic']}.json"
    )
    return Cell(
        name=name, chips=entry["chips"], config_name=entry["config"],
        traffic_name=entry["traffic"], config=config, traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"] if applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if applies(m, name)),
    )
