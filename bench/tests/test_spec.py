"""Every name in BENCHMARK.json resolves to its files; a new configuration,
traffic mix and per-layer metric are picked up as files and entries alone;
the traffic generator is deterministic and stays in the vocabulary."""
import json
import os

import numpy as np
import pytest

from bench import spec, traffic
from bench.tests import tiny

REPO = tiny.REPO
SPEC = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_each_workload_resolves(workload):
    cell = spec.resolve(REPO, workload)
    assert cell.config["name"] == cell.config_name
    assert cell.traffic["batch"] % cell.traffic["microbatches"] == 0
    assert set(cell.limits) == {"loss", "grad", "update"}
    assert hasattr(cell.reference(), "make_train_step")
    for m in cell.per_layer:
        reader = cell.metric_reader(m["name"])
        assert callable(reader.read) and isinstance(reader.READS, dict)
    names = {m["name"] for m in cell.end_to_end}
    assert {"setup_s", "tokens_per_s"} <= names
    assert cell.per_layer


def test_paths_and_names_follow_the_contract():
    assert SPEC["command"][1].startswith(SPEC["paths"][0] + "/")
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/configs/")
        conf = json.load(open(os.path.join(REPO, c["file"])))
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        assert set(m.get("workloads", cells)) <= cells
    assert all(0.01 <= m["bound"] <= 0.25 for m in SPEC["end_to_end"])


def test_new_config_traffic_and_metric_are_files_only(tmp_path):
    bench = tiny.make(str(tmp_path), extra_metric="steps_seen")
    with open(os.path.join(bench, "metrics", "steps_seen.py"), "w") as f:
        f.write('READS = {"host": "steps"}\n\n\n'
                'def read(run):\n    return float(run.steps)\n')
    cell = spec.resolve(str(tmp_path), "tiny.train", bench)
    assert cell.config["d_model"] == 64 and cell.traffic["batch"] == 8
    names = [m["name"] for m in cell.per_layer]
    assert "steps_seen" in names
    reader = cell.metric_reader("steps_seen")
    assert reader.read(type("R", (), {"steps": 7})()) == 7.0


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        spec.resolve(REPO, "no.such.cell")


@pytest.mark.parametrize("mix", sorted(os.listdir(os.path.join(REPO, "bench",
                                                               "traffic"))))
def test_traffic_is_deterministic_and_in_vocabulary(mix):
    m = dict(spec.load_json(os.path.join(REPO, "bench", "traffic", mix)),
             pool_batches=2, batch=4)
    vocab = 4016
    a = traffic.make_pool(m, vocab, 2**31 + 123)
    b = traffic.make_pool(m, vocab, 2**31 + 123)
    c = traffic.make_pool(m, vocab, 2**31 + 124)
    assert a.shape == (2, 4, m["seq_len"]) and a.dtype == np.int32
    np.testing.assert_array_equal(a, b)
    assert (a != c).mean() > 0.5
    assert a.min() >= 0 and a.max() < vocab
    # Zipf: the most common id is id 0, and distinct rows differ
    assert np.bincount(a.ravel()).argmax() == 0
    assert len({r.tobytes() for r in a.reshape(-1, m["seq_len"])}) == 8
