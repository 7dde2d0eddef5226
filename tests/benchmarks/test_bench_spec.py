"""BENCHMARK.json against the contract's shape, and the harness as data:
a cell, a configuration, a traffic mix and a per-layer metric are each
added by new files plus one new entry, with no edit to a file that is
there.  The contract tests run twice (``bench_root``, conftest.py): on
the real checkout, and on a copy in which such entries were appended."""

import json
import os
import re

import pytest

from benchmarks.harness import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


TRAIN_CELL = "opt-350m.train-seq2048"


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(ROOT)


@pytest.fixture
def contract(bench_root):
    """(checkout, its ``BENCHMARK.json``): real, then real + appended."""
    return bench_root, spec.load_benchmark(bench_root)


def cells_of(metric, bench):
    return set(metric.get("workloads")
               or [w["name"] for w in bench["workloads"]])


def test_top_level_keys_and_limits(contract):
    root, bench = contract
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 64 * 1024
    # a full check with 24 cells must fit into 43200 s
    n, rs = 24, bench["run_seconds"]
    assert (2 + 14 * n) * (rs + 60) + n * 2 * 90 + 1200 <= 43200
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_entries_have_just_the_contract_keys(contract):
    root, bench = contract

    def one_line(text):
        return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text

    assert all(one_line(word) for word in bench["command"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["why"]) and one_line(c["source"])
        assert len(c["reduced"]) <= 16
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert os.path.isfile(os.path.join(root, c["file"]))
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and one_line(w["why"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and one_line(m["layer"])


def test_names_and_units_use_the_allowed_characters(contract):
    _, bench = contract
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names += [e["name"] for e in bench[group]]
    names += [w["traffic"] for w in bench["workloads"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    for name in names:
        assert NAME.match(name), name
    metrics = bench["end_to_end"] + bench["per_layer"]
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert len({w["name"] for w in bench["workloads"]}) == len(
        bench["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_every_moves_names_a_metric_its_cells_report(contract):
    _, bench = contract
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        assert cells_of(m, bench) <= cells_of(e2e[m["moves"]], bench), m


def test_every_cell_reports_setup_and_more(contract):
    _, bench = contract
    used_configs = set()
    for w in bench["workloads"]:
        reported = [m["name"] for m in bench["end_to_end"]
                    if w["name"] in cells_of(m, bench)]
        assert "setup_s" in reported and len(reported) >= 2, w
        assert any(w["name"] in cells_of(m, bench)
                   for m in bench["per_layer"]), w
        used_configs.add(w["config"])
    assert used_configs == {c["name"] for c in bench["configs"]}


def test_every_cell_resolves_and_its_files_agree(contract):
    root, bench = contract
    for w in bench["workloads"]:
        cell = spec.resolve_cell(root, w["name"])
        assert cell.traffic["kind"] in spec.DRIVERS
        assert cell.per_layer and cell.end_to_end
        for metric in cell.per_layer:
            assert callable(spec.resolve_dotted(metric["reader"]))
    for c in bench["configs"]:
        with open(os.path.join(root, c["file"])) as f:
            config = json.load(f)
        assert config["source"] == c["source"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        assert callable(spec.resolve_dotted(config["builder"]))


def config_files():
    folder = os.path.join(ROOT, "benchmarks", "configs")
    return sorted(os.path.join(folder, f) for f in os.listdir(folder))


@pytest.mark.parametrize("path", config_files(), ids=os.path.basename)
def test_no_width_is_cut(path, bench):
    config = spec.load_json(path)
    spec.check_against_source(config)
    named = [c for c in bench["configs"]
             if os.path.join(ROOT, c["file"]) == path]
    # a preset of no source is never a cell's configuration
    assert bool(named) != bool(config.get("preset"))
    for c in named:
        assert sorted(c["reduced"]) == sorted(config["reduced"])


@pytest.mark.parametrize("break_it,why", [
    (lambda c: c.update(hidden_size=512), "a width differs"),
    (lambda c: c["reduced"].update(ffn_dim="4096 -> 1024"), "a width listed"),
    (lambda c: c.update(vocab_size=8), "a key differs, not in reduced"),
    (lambda c: c["builder_kwargs"].update(num_layers=2), "a builder argument"),
    (lambda c: c["harness"].update(vocab=100), "a harness key"),
    (lambda c: c.update(preset=True), "a preset with something published"),
    (lambda c: c.update(widths=[]), "no width named"),
    (lambda c: c["reduced"].update(max_position_embeddings="halved"),
     "the note lacks the published value"),
])
def test_the_width_check_fails_what_it_should(break_it, why):
    """On OPT's own serving file, the one with a key in ``reduced``."""
    config = spec.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                         "opt-350m-serve.json"))
    spec.check_against_source(config)
    break_it(config)
    with pytest.raises(spec.SpecError):
        spec.check_against_source(config)


def test_unknown_names_fail(bench):
    with pytest.raises(spec.SpecError):
        spec.resolve_cell(ROOT, "no-such.cell")
    with pytest.raises(spec.SpecError):
        spec.resolve_dotted("benchmarks.harness.readers:no_such_reader")


def test_a_cell_config_traffic_and_metric_are_added_by_files_alone(
        appended_checkout):
    """A later PR's move, in a temporary copy (conftest.py): new files
    under benchmarks/ and new entries in BENCHMARK.json; no existing file
    of benchmarks/ is edited, and the harness finds each by name."""
    root, before = appended_checkout
    cell = spec.resolve_cell(root, "dummy-model.dummy-traffic")
    assert cell.config["name"] == "dummy-model"
    assert cell.traffic["rate_per_s"] == 3
    names = [m["name"] for m in cell.per_layer]
    assert names[0] == "search.compile_s" and names[-1] == "dummy.metric"
    assert sum(n.startswith("setup.") for n in names) == 6
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s",
                                                    "setup_s"]
    for path, content in before.items():
        with open(path, "rb") as f:
            assert f.read() == content, path
    # the cells that were there resolve as before
    old, real = (spec.resolve_cell(r, TRAIN_CELL) for r in (root, ROOT))
    assert [m["name"] for m in old.per_layer] == [
        m["name"] for m in real.per_layer]
    assert old.end_to_end == real.end_to_end


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(capsys):
    from benchmarks import run

    rc = run.main(["--workload", TRAIN_CELL, "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""          # no result line, nothing else either
    assert "TPU" in out.err
