"""Run with ``JAX_PLATFORMS=cpu python -m pytest bench/tests`` from the
root of the checkout."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session")
def bench_json() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="session")
def small_config() -> dict:
    """GPT-7B on 16 GPUs (TP8-DP1-PP2): the program's off-table preset
    ``training_scenario(n_gpus=16)``, small enough for the packet loop."""
    return json.loads((DATA / "gpt7b-16.json").read_text())
