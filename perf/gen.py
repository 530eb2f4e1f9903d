"""Traffic generator: corpora and POST sequences from a seed.

One general generator for every cell.  A configuration file says what
the deployment holds (``data``, whose ``generator`` names the file under
``perf/generators/`` that makes its records and their edits); a traffic
file says how pipes post to it (``loop``, pipes, batch size, edit
share).  Nothing here imports JAX or the program: the client process and
the harness both build the same inputs from ``--seed``.

Seeds.  The data (which records, which ids, which edits) comes from the
run's seed.  The amount of work does not: batch sizes and edit counts
are the traffic file's, and each dataset's source order is a seeded
shuffle, so that a window meets the generator's duplicates at their
share of the corpus whatever the offset it starts from.
"""

from __future__ import annotations

import random

import plugins
from reference import parse_service


def generator(config: dict):
    """The configuration's record generator: ``perf/generators/<name>.py``
    for ``data.generator``, which offers ``corpus(data, datasets, seed)``,
    ``EDIT_KINDS``, ``edit(rng, row, kind)`` and ``is_duplicate(row_id,
    data)``."""
    return plugins.load("generators", config["data"]["generator"])


def corpus(config: dict, seed: int) -> dict:
    """{dataset_id: rows} preloaded into the deployment, each dataset in
    its source's order: a seeded shuffle.  (In id order the duplicates
    sit at the end, and a re-sync window that starts among them finds
    several times the links of one that does not: PERF.md.)"""
    datasets = parse_service(config["service_xml"])["datasets"]
    out = generator(config).corpus(config["data"], datasets, seed)
    for ds, ds_rows in out.items():
        random.Random(f"{seed}:order:{ds}").shuffle(ds_rows)
    return out


def perturb(rng: random.Random, row: dict, module) -> dict:
    """A copy of ``row`` with one edit of a kind the generator offers."""
    kind = rng.choice(module.EDIT_KINDS)
    return module.edit(rng, row, kind)


class Post:
    """One POST: the dataset it goes to and its entities."""

    __slots__ = ("dataset", "entities")

    def __init__(self, dataset: str, entities: list):
        self.dataset = dataset
        self.entities = entities


# -- closed loop: re-sync ------------------------------------------------------


def resync_pipes(config: dict, traffic: dict, seed: int, rows: dict):
    """One POST list per pipe: the pipe's dataset re-posted in source
    order from a seed-chosen offset, ``batch`` records at a time, with an
    exact ``edit_share`` of the records carrying one of the generator's
    edits.  Warm-up takes the first POSTs of each list and the window goes
    on from there."""
    rng = random.Random(f"{seed}:resync")
    module = generator(config)
    batch = traffic["batch"]
    pipes = []
    for ds in parse_service(config["service_xml"])["datasets"]:
        ds_rows = rows[ds]
        n = len(ds_rows)
        start = rng.randrange(n)
        order = ds_rows[start:] + ds_rows[:start]
        edited = set(rng.sample(range(n), round(n * traffic["edit_share"])))
        posted = [perturb(rng, r, module) if i in edited
                  else r for i, r in enumerate(order)]
        for _ in range(traffic["pipes_per_dataset"]):
            pipes.append([Post(ds, posted[s:s + batch])
                          for s in range(0, n, batch)])
    return pipes
