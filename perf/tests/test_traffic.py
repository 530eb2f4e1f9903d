"""Each traffic mix yields the same POSTs for the same seed, and every
seed the same amount of work."""

import json
import os

import pytest

import gen

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(*parts):
    with open(os.path.join(PERF, *parts)) as f:
        return json.load(f)


def cells():
    """Every configuration file under every traffic mix, in a cell yet
    or not."""
    return [(f"{c[:-5]}.{t[:-5]}", f"perf/configs/{c}", t[:-5])
            for c in sorted(os.listdir(os.path.join(PERF, "configs")))
            for t in sorted(os.listdir(os.path.join(PERF, "traffic")))]


def posts(config_file, mix, seed):
    config = load("..", config_file)
    config["data"]["records"] = 4000
    traffic = load("traffic", f"{mix}.json")
    rows = gen.corpus(config, seed)
    return [(p.dataset, p.entities)
            for pipe in gen.resync_pipes(config, traffic, seed, rows)
            for p in pipe]


@pytest.mark.parametrize("cell,config_file,mix", cells())
def test_same_seed_same_posts(cell, config_file, mix):
    seed = 2**31 + 12345
    assert posts(config_file, mix, seed) == posts(config_file, mix, seed)


@pytest.mark.parametrize("cell,config_file,mix", cells())
def test_seeds_differ_in_data_not_in_work(cell, config_file, mix):
    a = posts(config_file, mix, 7)
    b = posts(config_file, mix, 8)
    assert a != b
    assert sorted(len(e) for _, e in a) == sorted(len(e) for _, e in b)


@pytest.mark.parametrize("cell,config_file,mix", cells())
def test_every_offset_meets_the_duplicates_at_their_share(cell, config_file,
                                                           mix):
    """A source's order spreads the generator's duplicates (which it may
    make last), so the first POST of every pipe holds about the corpus's
    share of them, whatever the seed's offset."""
    config = load("..", config_file)
    config["data"]["records"] = 4000
    module = gen.generator(config)

    def share(rows):
        return sum(module.is_duplicate(r["_id"], config["data"])
                   for r in rows) / len(rows)

    for seed in (7, 8, 2**31 + 5):
        corpus = [r for rows in gen.corpus(config, seed).values()
                  for r in rows]
        want = share(corpus)
        assert 0 < want < 1, (seed, want)
        if "dup_rate" in config["data"]:
            assert abs(want - config["data"]["dup_rate"]) < 1e-3, want
        for p, _ in zip(posts(config_file, mix, seed)[::2], range(4)):
            assert abs(share(p[1]) - want) < 0.06, (seed, share(p[1]), want)
