"""Tests of the benchmark, on the CPU at toy sizes.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

The repository's own test run collects only ``tests/``; these run by
hand with the command above.
"""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def make_root(tmp: Path, config: str, mix: str, cell: str = "tiny.cell"):
    """A checkout holding one toy cell: ``config`` (with its module,
    where ``testdata`` has one) and ``mix`` from ``testdata``, the
    program through a link to ``src``."""
    (tmp / "chipbench" / "traffic").mkdir(parents=True)
    configs = tmp / "chipbench" / "configs"
    configs.mkdir(parents=True)
    shutil.copy(BENCH / "testdata" / f"{config}.json", configs)
    module = BENCH / "testdata" / f"{config}.py"
    if module.is_file():
        shutil.copy(module, configs)
    shutil.copy(BENCH / "testdata" / f"{mix}.json",
                tmp / "chipbench" / "traffic" / f"{mix}.json")
    (tmp / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": config, "source": "test", "reduced": [],
                         "file": f"chipbench/configs/{config}.json",
                         "why": "test"}]
    bench["workloads"] = [{"name": cell, "config": config, "traffic": mix,
                           "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture
def tiny_root(tmp_path):
    return lambda config, mix: make_root(tmp_path, config, mix)
