"""The generator keeps Table 1's means, its schedules repeat per seed,
no user's history outgrows the configuration's ``max_baskets``, and the
read stream leaves the other streams as they were."""
import hashlib
import json
import os

import numpy as np
import pytest

import gen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TABLE_1 = {"tafeng": (5.7, 6.2), "valuedshopper": (56.9, 9.1)}
CELLS = {"tafeng": ["ingest", "serve"], "valuedshopper": ["forget"]}
FIELDS = ("ev_due", "ev_user", "ev_items", "ev_pos", "forget_due",
          "forget_user", "read_due", "read_user")
# sha256 of the event and forget arrays of the 30 s schedules, as the
# generator made them before it had a read stream
PINNED = {
    ("tafeng", "ingest", 7):
        "238506bc0a230f937e1581cdbdc0c6a05c6731944ada96cc3de96a0117a1c9bd",
    ("tafeng", "ingest", 2147483999):
        "18dfbcd39d25b397ee93acbd616b46fb8e9a9ffc0e0641df2e7a63699e2b397f",
    ("valuedshopper", "forget", 7):
        "8e26c3153f6b46cbd1988ef4df19ab3ac2b5c5ad5256bf159675de4cd80611f6",
    ("valuedshopper", "forget", 2147483999):
        "85a7063cb2421051778a26cb4029b7bfc811d9589b1a0b86d11705ea0bf76270",
}


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
        for f in FIELDS:
            assert np.array_equal(getattr(a, f), getattr(b, f)), f
        # fixed intervals: the due times depend on the traffic alone
        assert np.array_equal(a.ev_due[a.ev_pos < 0], c.ev_due[c.ev_pos < 0])
        assert np.array_equal(a.read_due, c.read_due)
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


@pytest.mark.parametrize("key", sorted(PINNED), ids=lambda k: "-".join(
    map(str, k)))
def test_schedules_without_reads_are_as_pinned(key):
    name, cell, seed = key
    cfg = _json("configs", name + ".json")
    traffic = _json("traffic", cell + ".json")
    s = gen.schedule(cfg, traffic, gen.histories(cfg, seed), seed,
                     traffic["warm_s"] + 30.0)
    h = hashlib.sha256()
    for f in FIELDS[:6]:
        a = getattr(s, f)
        h.update(f.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PINNED[key]
    assert s.read_due.size == s.read_user.size == 0


def test_reads_are_drawn_by_basket_count_and_leave_the_rest_alone():
    cfg = _json("configs", "tafeng.json")
    hist = gen.histories(cfg, 17)
    serve = _json("traffic", "serve.json")
    quiet = {k: v for k, v in serve.items() if not k.startswith("read_")}
    t_end = serve["warm_s"] + 30.0
    a = gen.schedule(cfg, serve, hist, 17, t_end)
    b = gen.schedule(cfg, quiet, hist, 17, t_end)
    for f in FIELDS[:6]:
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    assert b.read_due.size == 0
    rate = serve["read_rate"]
    assert a.read_due.size == int(t_end * rate)
    assert np.allclose(np.diff(a.read_due), 1.0 / rate)
    # a user's share of the reads follows its basket count
    n_b = hist.n_baskets
    reads = np.bincount(a.read_user, minlength=n_b.size)
    for lo, hi in ((2, 4), (8, 20)):
        mine = (n_b >= lo) & (n_b <= hi)
        want = n_b[mine].sum() / n_b.sum()
        assert abs(reads[mine].sum() / reads.sum() / want - 1) < 0.1


def test_forgotten_users_are_never_read():
    cfg = _json("configs", "valuedshopper.json")
    hist = gen.histories(cfg, 19)
    traffic = dict(_json("traffic", "forget.json"), read_rate=400,
                   forget_interval_s=0.05)
    s = gen.schedule(cfg, traffic, hist, 19, traffic["warm_s"] + 30.0)
    assert s.forget_user.size > 500 and s.read_user.size > 10000
    assert not np.isin(s.read_user, s.forget_user).any()
