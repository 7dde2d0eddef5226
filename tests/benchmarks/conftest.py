"""Two checkouts for the tests that hold ``BENCHMARK.json`` to its
contract: the real one, and a temporary copy in which a later PR's move
has been made — a configuration, a cell, a traffic file and a per-layer
metric APPENDED by new files and new entries, the new cell joined to the
lists every cell is in.  A test that passes on the first and fails on
the second pins a place in the file, and would stop that PR."""

import json
import os
import shutil

import pytest

from benchmarks.harness import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DUMMY_CELL = "dummy-model.dummy-traffic"


def entry_named(entries, name):
    """The one entry of a ``BENCHMARK.json`` list with this name."""
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, f"{name!r} is listed {len(found)} times"
    return found[0]


def files_under(folder):
    out = {}
    for parent, _, names in os.walk(folder):
        for name in names:
            with open(os.path.join(parent, name), "rb") as f:
                out[os.path.join(parent, name)] = f.read()
    return out


@pytest.fixture(scope="session")
def appended_checkout(tmp_path_factory):
    """(root, what every file of its ``benchmarks/`` held before the
    dummy files were added)."""
    root = str(tmp_path_factory.mktemp("appended"))
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = files_under(os.path.join(root, "benchmarks"))

    def put(rel, obj):
        path = os.path.join(root, "benchmarks", rel)
        assert path not in before, "a later PR edits no file that is there"
        with open(path, "w") as f:
            json.dump(obj, f)

    bench = spec.load_benchmark(ROOT)
    config = spec.load_json(os.path.join(
        ROOT, entry_named(bench["configs"], "opt-350m-serve")["file"]))
    config["name"] = "dummy-model"
    put("configs/dummy-model.json", config)
    put("traffic/dummy-traffic.json",
        {"kind": "open_loop", "rate_per_s": 3, "arrival": "poisson",
         "warmup_s": 1, "prompt_tokens": {"dist": "const", "value": 8},
         "max_new_tokens": {"dist": "const", "value": 4}, "why": "dummy"})
    put("layer_metrics/dummy.metric.json",
        {"name": "dummy.metric", "unit": "s", "layer": "search + lowering",
         "moves": "setup_s",
         "reader": "benchmarks.harness.readers:compile_s"})
    bench["configs"].append({"name": "dummy-model", "source": config["source"],
                             "file": "benchmarks/configs/dummy-model.json",
                             "reduced": list(config["reduced"]), "why": "x"})
    bench["workloads"].append({"name": DUMMY_CELL, "config": "dummy-model",
                               "traffic": "dummy-traffic", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "dummy.metric", "unit": "s",
                               "better": "lower", "source": "host_clock",
                               "layer": "search + lowering",
                               "moves": "setup_s",
                               "workloads": [DUMMY_CELL]})
    # a new cell joins the lists that every cell is in (the six
    # ``setup.*``) and an end-to-end metric beside ``setup_s``
    everyone = {w["name"] for w in bench["workloads"]} - {DUMMY_CELL}
    for metric in bench["per_layer"]:
        if set(metric.get("workloads", ())) == everyone:
            metric["workloads"].append(DUMMY_CELL)
    entry_named(bench["end_to_end"],
                "serve_tokens_per_s")["workloads"].append(DUMMY_CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, before


@pytest.fixture(params=["real", "appended"])
def bench_root(request):
    """The checkout a contract test reads: the real one, then the copy
    with a later PR's entries appended."""
    if request.param == "real":
        return ROOT
    return request.getfixturevalue("appended_checkout")[0]


@pytest.fixture(scope="session")
def named():
    return entry_named
