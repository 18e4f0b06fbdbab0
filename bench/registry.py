"""Everything the harness knows about a cell, found by name.

``BENCHMARK.json`` lists the cells, configurations and metrics. A cell's
pieces are files under ``bench/``, each named after the entry it serves:

* ``configs/<config>.json``: the configuration's sizes, named by its
  ``file`` entry; it names its plain reference (``reference/<name>.py``),
  its work counts (``counts/<name>.py``) and its kernel's name pattern;
* ``traffic/<traffic>.json``: the traffic mix, read by ``loadgen.py``;
* ``metrics/<metric>.py``: one ``read(ctx)`` per metric, end-to-end and
  per-layer alike; it returns a number, or None where it finds nothing to
  read (the metric is then left out of the result line);
* ``peaks.json``: the chip's peaks, keyed by ``device_kind``.

Adding a cell, configuration, mix, metric or count is adding files and
entries; no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple  # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", (cell,))


def find_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, name))
    per_layer = tuple(m for m in bench["per_layer"] if _reports(m, name))
    return Cell(name, cell["chips"], config, traffic, e2e, per_layer)


def load_module(kind: str, name: str, root: Path = ROOT):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json; "
                       f"have {sorted(table['devices'])}")
    return table["devices"][device_kind]


def work(config: dict, entry: str) -> dict:
    """Compulsory ``{"bytes", "ops"}`` of one entry, from ``counts/``."""
    return load_module("counts", config["counts"][entry]).count(config)


def least_seconds(work_: dict, peak: dict) -> float:
    """The roofline's least time for ``work_`` on a chip with ``peak``: the
    larger of bytes over bandwidth and operations over the peak rate. Says
    on standard error which of the two binds."""
    t_bytes = work_["bytes"] / peak["hbm_bytes_per_s"]
    t_ops = work_["ops"] / peak["ops_per_s"]
    print(f"roofline bytes_s {t_bytes} ops_s {t_ops} binds {'bytes' if t_bytes >= t_ops else 'ops'}",
          file=sys.stderr)
    return max(t_bytes, t_ops)
