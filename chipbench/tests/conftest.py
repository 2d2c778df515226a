"""Tiny stand-ins for the benchmark's cells, for the CPU tests."""
import copy
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def tiny_spec(cell: str) -> tuple:
    """The files of ``<config>.<traffic>`` at a size the CPU runs in
    seconds: every rule of the traffic kept, the scale and the rates
    cut."""
    bench = _load(ROOT, "BENCHMARK.json")
    config, mix = cell.split(".")
    cfg = copy.deepcopy(_load(BENCH, "configs", config + ".json"))
    traffic = copy.deepcopy(_load(BENCH, "traffic", mix + ".json"))
    cfg.update(n_users=96, n_items=300, k_neighbors=8, batch_size=16,
               load_batch_size=64, baskets_per_user_cap=20,
               avg_baskets=min(cfg["avg_baskets"], 12.0), max_baskets=48)
    traffic.update(add_rate=min(traffic["add_rate"], 300.0),
                   add_burst=min(traffic["add_burst"], 16),
                   adds_per_user_cap=24, warm_s=1.0,
                   warm_add_bursts=min(traffic["warm_add_bursts"], 16),
                   del_user_frac=traffic["del_user_frac"] and 0.05)
    if traffic["forget_interval_s"]:
        traffic["forget_interval_s"] = 0.5
    if traffic.get("read_rate"):
        traffic.update(read_rate=min(traffic["read_rate"], 40.0),
                       read_check=min(traffic["read_check"], 24))
    return cfg, traffic, 1, bench


@pytest.fixture
def spec():
    return tiny_spec
