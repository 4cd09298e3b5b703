"""Small configurations for the benchmark's CPU tests."""

import copy
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny(config_name: str, n_sets: int = 1200, clusters: int = 12) -> dict:
    """A configuration file of the benchmark at a size a CPU test holds."""
    with open(os.path.join(BENCH, "configs", config_name + ".json")) as f:
        cfg = json.load(f)
    cfg = copy.deepcopy(cfg)
    cfg["n_sets"] = n_sets
    cfg["planted"]["clusters"] = clusters
    return cfg


@pytest.fixture
def dedup_cfg():
    return tiny("dblp-dedup")
