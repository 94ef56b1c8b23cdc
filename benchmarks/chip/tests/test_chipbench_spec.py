"""Every cell of BENCHMARK.json resolves to its parts by name, and a cell,
a configuration, a key distribution, a mix or a metric added as files
alone is found."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import spec, traffic  # noqa: E402

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell):
    c = spec.load_cell(cell, BENCH)
    w = {w["name"]: w for w in BENCH["workloads"]}[cell]
    assert c.config["name"] == w["config"]
    assert spec.traffic_file(w["traffic"]).is_file()
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.load_reader(m["name"]))


def test_benchmark_names_and_files():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert set(m["workloads"]) <= {w["name"] for w in BENCH["workloads"]}
    for e in BENCH["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25


def test_added_files_are_found_by_name(tmp_path):
    """A configuration with a key distribution of its own, a mix and a
    metric, each added as a new file, make a cell the harness runs."""
    root, bench_dir = tmp_path, tmp_path / "benchmarks" / "chip"
    shutil.copytree(spec.BENCH_DIR, bench_dir, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    (bench_dir / "traffic" / "tiny.json").write_text(json.dumps({"pool_batches": 2}))
    (bench_dir / "metrics" / "answer.py").write_text("def read(run):\n    return 42.0\n")
    (bench_dir / "keys" / "one_hot.py").write_text(
        "import numpy as np\n\n"
        "def column(rng, n, *, domain, params, batch, relation):\n"
        "    return np.full(n, params['value'] + batch, dtype=np.int64)\n"
    )
    config = json.loads((spec.ROOT / BENCH["configs"][0]["file"]).read_text())
    config.update(name="one_hot", keys={"kind": "one_hot", "value": 3}, batch_rows=64)
    (bench_dir / "configs" / "one_hot.json").write_text(json.dumps(config))
    bench["configs"].append(
        dict(BENCH["configs"][0], name="one_hot", file="benchmarks/chip/configs/one_hot.json")
    )
    bench["workloads"].append(
        {"name": "one_hot.tiny", "config": "one_hot", "traffic": "tiny", "chips": 1, "why": "t"}
    )
    bench["per_layer"].append(
        {"name": "answer", "unit": "x", "better": "higher", "source": "program_counter",
         "layer": "planner", "moves": "rows_per_s", "workloads": ["one_hot.tiny"]}
    )
    next(e for e in bench["end_to_end"] if e["name"] == "rows_per_s")["workloads"].append(
        "one_hot.tiny"
    )
    cell = spec.load_cell("one_hot.tiny", bench, root, bench_dir)
    assert cell.mix["pool_batches"] == 2
    assert [m["name"] for m in cell.per_layer] == ["answer"]
    assert spec.load_reader("answer", bench_dir)(None) == 42.0
    batch = traffic.make_batch(cell.config, cell.key_column, 1, 5)
    assert batch["R"].shape == (64, 2) and set(batch["R"][:, 1]) == {8}
    assert set(batch["S"][:, 0]) == {8}


def test_unknown_key_distribution_is_refused(tmp_path):
    with pytest.raises(FileNotFoundError):
        spec.load_key_column("no_such_kind", tmp_path)
