"""The generator keeps Table 1's means, its schedules repeat per seed,
and no user's history outgrows the configuration's ``max_baskets``."""
import json
import os

import numpy as np
import pytest

import gen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TABLE_1 = {"tafeng": (5.7, 6.2), "valuedshopper": (56.9, 9.1)}
CELLS = {"tafeng": ["ingest"], "valuedshopper": ["forget"]}


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=sorted(TABLE_1))
def deployment(request):
    cfg = _json("configs", request.param + ".json")
    return request.param, cfg, gen.histories(cfg, 11)


def test_means_match_table_1(deployment):
    name, cfg, hist = deployment
    baskets, items = TABLE_1[name]
    assert hist.n_baskets.size == cfg["n_users"]
    # at least 2 baskets per user, as the original generator: +0.5%
    assert abs(hist.n_baskets.mean() / baskets - 1) < 0.02
    # a basket is the union of pool and fresh items, so repeats between
    # the two shrink it below its Poisson draw (as in the original)
    sizes = (hist.items >= 0).sum(axis=1)
    assert abs(sizes.mean() / items - 1) < 0.05
    assert ((hist.items < cfg["n_items"]) & (hist.items >= -1)).all()
    for row in hist.items[:2000]:
        ids = row[row >= 0]
        assert ids.size and np.unique(ids).size == ids.size


def test_schedules_repeat_per_seed_and_differ_between_seeds(deployment):
    name, cfg, hist = deployment
    for cell in CELLS[name]:
        traffic = _json("traffic", cell + ".json")
        t_end = traffic["warm_s"] + 8.0
        a = gen.schedule(cfg, traffic, hist, 11, t_end)
        b = gen.schedule(cfg, traffic, hist, 11, t_end)
        c = gen.schedule(cfg, traffic, gen.histories(cfg, 12), 12, t_end)
        for f in ("ev_due", "ev_user", "ev_items", "ev_pos", "forget_due",
                  "forget_user"):
            assert np.array_equal(getattr(a, f), getattr(b, f)), f
        # fixed intervals: the due times depend on the traffic alone
        assert np.array_equal(a.ev_due[a.ev_pos < 0], c.ev_due[c.ev_pos < 0])
        assert not np.array_equal(a.ev_user, c.ev_user)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_histories_fit_max_baskets(name):
    cfg = _json("configs", name + ".json")
    for cell in CELLS[name]:
        traffic = _json("traffic", cell + ".json")
        assert gen.max_history(cfg, traffic) <= cfg["max_baskets"]
    hist = gen.histories(cfg, 13)
    for cell in CELLS[name]:
        traffic = _json("traffic", cell + ".json")
        s = gen.schedule(cfg, traffic, hist, 13, traffic["warm_s"] + 30.0)
        adds = np.bincount(s.ev_user[s.ev_pos < 0], minlength=cfg["n_users"])
        assert (hist.n_baskets + adds).max() <= cfg["max_baskets"]
        assert (hist.n_baskets <= cfg["baskets_per_user_cap"]).all()
