"""A copy of the benchmark with tiny cells added as data, for tests on
the CPU: the same harness, reference and comparison, at a size a test
run holds."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

from fleetbench.spec import BENCH_DIR, ROOT

TINY_FRAMES = 20


def tiny_root(dest: Path, *, sites: int = 8, mesh_shards: int = 0,
              scenarios=("weighted4", "uniform")) -> Path:
    """``dest`` gets ``BENCHMARK.json`` and ``bench/`` with two more
    configurations (``tiny4``, ``tiny8``: the paper site at
    ``TINY_FRAMES`` frames, with its 4 devices and with 8) and one more
    mix (``tiny``) in two cells, ``tiny4.tiny`` and ``tiny8.tiny``.  No
    existing file is edited."""
    dest = Path(dest)
    shutil.copytree(BENCH_DIR, dest / "bench", ignore=shutil.ignore_patterns(
        ".jax_cache", "__pycache__", "out"))
    with open(ROOT / "BENCHMARK.json") as f:
        doc = json.load(f)
    for name, n_devices in (("tiny4", 4), ("tiny8", 8)):
        with open(BENCH_DIR / "configs" / "paper_site.json") as f:
            conf = json.load(f)
        conf["site"]["n_frames"] = TINY_FRAMES
        conf["site"]["n_devices"] = n_devices
        conf["name"] = name
        with open(dest / "bench" / "configs" / f"{name}.json", "w") as f:
            json.dump(conf, f)
        doc["configs"].append({
            "name": name, "source": conf["source"],
            "file": f"bench/configs/{name}.json",
            "reduced": ["n_frames", "n_devices"], "why": "a test size",
        })
    groups = len(scenarios)
    with open(dest / "bench" / "traffic" / "tiny.json", "w") as f:
        json.dump({"scenarios": list(scenarios), "congestion_levels": [0.3],
                   "n_seeds": sites, "batch_size": sites * groups,
                   "mesh_shards": mesh_shards}, f)
    for conf in ("tiny4", "tiny8"):
        doc["workloads"].append({
            "name": f"{conf}.tiny", "config": conf, "traffic": "tiny",
            "chips": 1, "why": "a test size",
        })
    with open(dest / "BENCHMARK.json", "w") as f:
        json.dump(doc, f, indent=1)
    return dest
