"""The copied reference against the program's per-key reference, and the
incremental window against a recount from scratch."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import reference, spec, traffic  # noqa: E402

CONFIGS = [c["name"] for c in spec.load_benchmark()["configs"]]


def small(config_name: str, rows: int = 2048):
    """A configuration of BENCHMARK.json at ``rows`` R rows a batch over a
    4096-value domain, with its key generator."""
    bench = spec.load_benchmark()
    cfg_file = {c["name"]: c["file"] for c in bench["configs"]}[config_name]
    config = json.loads((spec.ROOT / cfg_file).read_text())
    config.update(domain=4096, batch_rows=rows)
    return config, spec.load_key_column(config["keys"]["kind"])


@pytest.mark.parametrize("name", CONFIGS)
def test_matches_program_reference(name):
    from repro.core import make_query
    from repro.mapreduce import keyed_join_fingerprint

    config, keys = small(name)
    shape = reference.JoinShape(config["relations"])
    query = make_query({k: tuple(v) for k, v in config["relations"].items()})
    for i in range(3):
        batch = traffic.make_batch(config, keys, 2**31 + 11, i)
        assert reference.keyed_fingerprint(shape, batch) == keyed_join_fingerprint(query, batch)


@pytest.mark.parametrize("name", CONFIGS)
def test_window_retraction_matches_recount(name):
    config, keys = small(name, rows=1024)
    shape = reference.JoinShape(config["relations"])
    window = 3
    ref = reference.WindowReference(shape, config["domain"], window)
    batches = [traffic.make_batch(config, keys, 5, i) for i in range(8)]
    prev = (0, 0)
    for i, batch in enumerate(batches):
        delta, count, checksum = ref.add(batch)
        kept = batches[max(0, i - window + 1): i + 1]
        data = {nm: np.concatenate([b[nm] for b in kept]) for nm in batch}
        assert (count, checksum) == reference.keyed_fingerprint(shape, data)
        older = {nm: np.concatenate([b[nm] for b in kept[:-1]] or [batch[nm][:0]]) for nm in batch}
        assert delta == count - reference.keyed_fingerprint(shape, older)[0]
        prev = (count, checksum)
    assert prev != (0, 0)


@pytest.mark.parametrize("name", CONFIGS)
def test_same_seed_same_batches_and_seeds_differ(name):
    config, keys = small(name)
    a = traffic.make_batch(config, keys, 2**32 + 5, 3)
    b = traffic.make_batch(config, keys, 2**32 + 5, 3)
    c = traffic.make_batch(config, keys, 2**32 + 6, 3)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["R"], c["R"])


def test_paper_hh_sizes_and_heavy_hitter():
    config, keys = small("paper_hh", rows=1000)
    batch = traffic.make_batch(config, keys, 1, 0)
    assert traffic.batch_rows(config) == {"R": 1000, "S": 100}
    assert (batch["R"][:, 1] == 7).sum() == 100 and (batch["S"][:, 0] == 7).sum() == 10


def test_paper_hh_window_is_the_papers_relations():
    config, _ = small("paper_hh")
    config["batch_rows"] = spec.load_cell("paper_hh.bulk").config["batch_rows"]
    window = traffic.window_batches(config)
    rows = traffic.batch_rows(config)
    assert (window * rows["R"], window * rows["S"]) == (10**6, 10**5)
