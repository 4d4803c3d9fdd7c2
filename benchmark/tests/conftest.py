import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]
os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture(autouse=True)
def served_cell(monkeypatch):
    """The served cell is not in BENCHMARK.json yet (PERF.md, Open
    questions); the tests still drive its whole path, so the harness is
    shown BENCHMARK.json with the cell's entries (``served_cell.json``)
    added."""
    import run

    with open(os.path.join(os.path.dirname(__file__), "served_cell.json")) as f:
        extra = json.load(f)
    load = run.load_json

    def load_json(path):
        data = load(path)
        if os.path.basename(path) == "BENCHMARK.json":
            for key, entries in extra.items():
                data[key] = data[key] + entries
        return data

    monkeypatch.setattr(run, "load_json", load_json)
