"""A scratch checkout for the harness's end-to-end tests: the
repository's ``BENCHMARK.json`` and ``bench/`` copied, ``src/`` linked,
plus a throwaway configuration at a size the CPU runs in seconds."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
TINY = {"model": {"widths": [8, 16, 24, 32, 48], "depths": [1, 1, 1, 1, 1],
                  "head_widths": [64, 64], "num_classes": 10},
        "image_size": 32}


def make(tmp: Path, with_src: bool = True) -> Path:
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    if with_src:
        os.symlink(ROOT / "src", tmp / "src")
    return tmp


def tiny_config(base: str, name: str) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{base}.json").read_text())
    cfg["model"].update(TINY["model"])
    cfg.update(name=name, image_size=TINY["image_size"])
    return cfg


def add_cell(root: Path, config: dict, traffic_name: str, traffic: dict,
             metrics=()) -> str:
    """New files and entries only: a configuration, a traffic mix, a
    cell, and per-layer metrics (name -> reader source)."""
    (root / "bench" / "configs" / f"{config['name']}.json").write_text(
        json.dumps(config))
    (root / "bench" / "traffic" / f"{traffic_name}.json").write_text(
        json.dumps(traffic))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = f"{config['name']}.{traffic_name}"
    bench["configs"].append({
        "name": config["name"], "source": "test", "reduced": [],
        "file": f"bench/configs/{config['name']}.json", "why": "test"})
    bench["workloads"].append({"name": cell, "config": config["name"],
                               "traffic": traffic_name, "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "images_per_s":
            m["workloads"].append(cell)
    for name, source in dict(metrics).items():
        (root / "bench" / "metrics" / f"{name}.py").write_text(source)
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "test",
            "moves": "images_per_s", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell


DRIVER = """
import sys
sys.path.insert(0, {src!r})
sys.path.insert(0, {bench!r})
{patch}
import run
sys.exit(run.main(sys.argv[1:], require_tpu=False))
"""


def run_cell(root: Path, cell: str, seed: int, seconds: float, trace: int,
             patch: str = "", timeout: float = 600):
    """The harness in a child process with its look for a TPU skipped;
    returns (returncode, result or None, stderr)."""
    code = DRIVER.format(src=str(root / "src"), bench=str(root / "bench"),
                         patch=patch)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", code, "--workload", cell, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if p.returncode == 0 and lines else None
    return p.returncode, result, p.stderr
