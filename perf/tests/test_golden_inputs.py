"""Both configurations under ``resync`` build, for each seed, the very
corpus and POST lists that ``perf/gen.py`` built at commit
9d9078175ce65b68581c3013c77cd8bc0e791a4e, before the record generators
moved into ``perf/generators/``: the SHA-256 of their JSON, at a test
size and at the configurations' own 100,000 records."""

import hashlib
import json
import os

import pytest

import gen

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (configuration, data.records, seed): sha256 of json.dumps([corpus, pipes])
GOLDEN = {
    ("sesam-dedup", 4000, 7):
        "fe44518b8d7709b1486c9274d2d4397ecc08c8bac986b119817177644d0569d4",
    ("sesam-dedup", 4000, 2**31 + 7):
        "ae61b2529dc01030fcb87fbc48298f32780acad57e1fb1a8d40a973e0718dbe4",
    ("sesam-dedup", 4000, 2147499101):
        "ef31807033e2eff345814ef45993371b0b6d118d8b6522319e62f3e5959762c4",
    ("sesam-dedup", 100000, 7):
        "bef91eb1a96f026359b12e4a9c8822ef5e10acdeb1cfe279969a52507e364e01",
    ("sesam-dedup", 100000, 2**31 + 7):
        "711a695beb9627201db749d2bdb8de91130571c0076a465f08dd10ff33a45f8a",
    ("sesam-dedup", 100000, 2147499101):
        "25c246818587ab32f9ca4c3d75181f86ecba38f38b7440752d8cb560362a5d0b",
    ("sesam-linkage", 4000, 7):
        "9f43997aee9b7a23b1bb955d278354b3980812e06492fa867fa7687c7eebbe9a",
    ("sesam-linkage", 4000, 2**31 + 7):
        "62e0b4bc8c0f264092252c61dcfd1721d86016447c64e353dfe73912e385b60c",
    ("sesam-linkage", 4000, 2147499101):
        "885dfe47bf2e4212bc8c8401f0df26daa216e64a6f7ffc65bc474ec19a9ba27e",
    ("sesam-linkage", 100000, 7):
        "bf91ec172154bbec2a9e0c43f3077cbcf8d8042bfbed2ac6d287d60603da154f",
    ("sesam-linkage", 100000, 2**31 + 7):
        "162e40500282f24382e1589ae8ca8a26df0c760319e69b085d9cf5b93b426b66",
    ("sesam-linkage", 100000, 2147499101):
        "a8626d6a824029c768f9c18b7d26f57595c1ed16455d2e9393fdc68a13f5b05a",
}


def load(*parts):
    with open(os.path.join(PERF, *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("config_name,records,seed", sorted(GOLDEN))
def test_resync_inputs_are_the_parents(config_name, records, seed):
    config = load("configs", f"{config_name}.json")
    config["data"]["records"] = records
    traffic = load("traffic", "resync.json")
    rows = gen.corpus(config, seed)
    pipes = gen.resync_pipes(config, traffic, seed, rows)
    blob = json.dumps([rows, [[[p.dataset, p.entities] for p in pipe]
                              for pipe in pipes]])
    digest = hashlib.sha256(blob.encode()).hexdigest()
    assert digest == GOLDEN[(config_name, records, seed)]
