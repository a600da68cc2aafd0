"""BENCHMARK.json and the data files it names fit together: every cell's
configuration and mix exist, every metric has a reader, every cell
reports set-up, another end-to-end metric and a per-layer metric."""
import re

import pytest

import spec
import traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_names_and_files(bench):
    assert bench["command"] == ["python3", "benchmarks/chip/run.py"]
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        cfile = spec.config_file(bench, c["name"])
        assert cfile["name"] == c["name"]
        assert sorted(cfile["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert key in cfile["published"] and key in cfile["cut"]
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        spec.config_file(bench, w["config"])
        assert traffic.load(w["traffic"])["kind"] in ("serve", "train")
        assert w["chips"] == 1


def test_every_metric_has_a_reader(bench):
    for section in ("end_to_end", "per_layer"):
        for m in bench[section]:
            assert NAME.match(m["name"])
            assert callable(spec.reader(m["name"]))


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    for w in bench["workloads"]:
        e2e = {m["name"] for m in spec.metrics_for(bench, w["name"],
                                                   "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = spec.metrics_for(bench, w["name"], "per_layer")
        assert layers
        for m in layers:
            assert m["moves"] in e2e


def test_limits_lie_between_their_readings(bench):
    for w in bench["workloads"]:
        for v in spec.limits(w["name"]).values():
            assert v["lower"] < v["limit"] < v["upper"]
