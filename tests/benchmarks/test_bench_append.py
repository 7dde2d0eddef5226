"""No test of the benchmark pins a PLACE in ``BENCHMARK.json``.

A later PR adds a cell or a per-layer metric by APPENDING entries (an
entry put anywhere else reads as a change to the one after it), and may
edit no file under the benchmark's ``paths`` — these tests among them.
So a test that asks what stands last, or first, in one of the file's
lists refuses every such PR (PERF.md section 6, PR 32 (e)): entries are
found by name (``named``, conftest.py).  The guard reads every test file
of this directory; the contract tests themselves run on a copy with
entries appended (``bench_root``, conftest.py)."""

import glob
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
LISTS = "configs|workloads|end_to_end|per_layer"
# one of the four lists' keys in brackets, then a whole number in
# brackets — whatever the variable, also over a line break
PINNED = re.compile(r'\[\s*"(?:%s)"\s*\]\s*\[\s*-?\d+\s*\]' % LISTS)
# the planted lines are put together here so that this file holds none
INDEXED = ['assert bench["per_layer"]' + "[-1] is entry",
           'config = load(bench["configs"]' + '[0]["file"])',
           'new["workloads"]' + "[ -1 ]",
           'cell = bench["workloads"]\n' + '    [2]["name"]',
           'first = spec.load_benchmark(ROOT)["end_to_end"]' + "[0]"]
BY_NAME = ['entry = named(bench["per_layer"], "serve.live_page_share")',
           'next(m for m in bench["per_layer"] if m["name"] == name)',
           'bench["workloads"].append(cell)',
           'cells = metric["workloads"]; last = cells[-1]',
           'for w in bench["workloads"][:]: pass']


def pinned_places(text: str, path: str = "<text>") -> list:
    """``path:line: the offending text`` of every index by a literal
    position into one of ``BENCHMARK.json``'s four lists."""
    return [f"{path}:{text.count(chr(10), 0, m.start()) + 1}: "
            f"{' '.join(m.group(0).split())}" for m in PINNED.finditer(text)]


def test_no_test_indexes_benchmark_json_by_position():
    found = []
    for path in sorted(glob.glob(os.path.join(HERE, "*.py"))):
        with open(path) as f:
            found += pinned_places(f.read(), os.path.relpath(path, HERE))
    assert not found, (
        "find the entry by its name (conftest.py `named`), not by where "
        "it stands — a later PR appends:\n" + "\n".join(found))


@pytest.mark.parametrize("text", INDEXED)
def test_the_guard_fails_a_planted_index(text):
    found = pinned_places("import os\n\n" + text + "\n", "planted.py")
    assert len(found) == 1 and found[0].startswith("planted.py:3: ")


@pytest.mark.parametrize("text", BY_NAME)
def test_the_guard_passes_what_finds_an_entry_by_name(text):
    assert pinned_places(text) == []
