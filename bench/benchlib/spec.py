"""Find a cell's files by name: ``BENCHMARK.json`` names the cell's
configuration and traffic mix; their files sit under ``bench/configs``
and ``bench/traffic``, a configuration's plain reference beside it
(``bench/configs/<reference>.py``), each per-layer metric's reader at
``bench/metrics/<name>.py``.

A configuration's file keeps the harness's keys at its top level
(``name``, ``source``, ``reference``, ``image_size``, ``precision``,
``matmul_precision``, ``peak``, ``control``, ``correct``, ``assumed``)
and the program's architecture in one ``model`` object: every field of
the program's ``EfficientViTConfig`` but ``name`` and ``image_size``
that the configuration sets (``system.model_config``; a key the program
has no field for refuses the cell before any weights are made).

A reference module (``bench/configs/<reference>.py``) is plain JAX that
imports nothing of the program, and gives

* ``init_params(key, cfg)``: the fp32 weights, in the tree the program
  serves, drawn from ``key`` inside one jitted call;
* ``images(key, n, size)``: ``n`` input images of ``size`` pixels;
* ``forward(params, x, cfg, *, quant_bits, products)``: the logits, at
  the configuration's arithmetic or at its control's;
* ``macs_per_image(cfg)``: the work of one image, counted from sizes.

A new configuration is added as new files only: its JSON, its reference
where no existing one computes it, and any per-layer metric readers;
then appends to ``BENCHMARK.json``'s ``configs``, ``workloads`` and the
metrics' ``workloads`` lists."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """A cell, configuration, traffic mix or metric that cannot be found
    or does not hold what the harness needs."""


def load_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError:
        raise SpecError(f"{path} not found") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: {e}") from None


def load_module(path: Path, name: str):
    """Import a Python file of the benchmark by its path."""
    if not path.is_file():
        raise SpecError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    end_to_end: list        # BENCHMARK.json metrics this cell reports
    per_layer: list

    @property
    def reference(self):
        """The configuration's plain reference module."""
        return load_module(BENCH_DIR / "configs"
                           / f"{self.config['reference']}.py",
                           f"bench_ref_{self.config['reference']}")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench.get("workloads", ())}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name}: no configuration "
                        f"{w['config']!r} in BENCHMARK.json")
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    per_layer = [m for m in bench["per_layer"] if _reports(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def metric_reader(name: str):
    """``read(run) -> float | None`` of one per-layer metric."""
    mod = load_module(BENCH_DIR / "metrics" / f"{name}.py",
                      f"bench_metric_{name.replace('.', '_')}")
    return mod.read
