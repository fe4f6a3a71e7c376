import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def read_json(kind, name):
    """A configuration or traffic file of bench/, by name."""
    with open(os.path.join(ROOT, "bench", kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def small_cell():
    """``tpch10m.q2-h1to7`` (or another configuration and traffic of
    bench/) at 20,000 rows with alpha 1,000: a two-layer hierarchy, two
    warm-up queries."""
    from bench.lib.harness import load_cell

    def make(config=None, traffic=None, rows=20_000, alpha=1_000):
        cell = copy.deepcopy(load_cell("tpch10m.q2-h1to7"))
        if config:
            cell.config = read_json("configs", config)
            cell.traffic = read_json("traffic", traffic)
        cell.config["rows"] = rows
        cell.config["alpha"] = alpha
        cell.traffic["warmup_queries"] = 2
        return cell
    return make
