"""The repo's front door names the benchmark the driver runs, and every
file that benchmark's declaration names is there and loads.

Reads ``BENCHMARK.json`` and the files it points at; starts no backend and
runs no cell (a run needs the chip). What fails here first: a per-layer
reader renamed or dropped while ``BENCHMARK.json`` still lists it (its
metric would read ``null`` in the ledger), a cell whose configuration,
reference, traffic file or driver is missing, and a document that sends a
reader to a benchmark, a record or a ``make`` target that does not exist.
"""

import glob
import os
import re

import pytest

from benchmark import common

ROOT = common.ROOT
DECLARED = common.load_json("BENCHMARK.json")
CELLS = {w["name"]: w for w in DECLARED["workloads"]}
CONFIGS = {c["name"]: c for c in DECLARED["configs"]}
END_TO_END = {m["name"] for m in DECLARED["end_to_end"]}


@pytest.mark.parametrize("entry", DECLARED["per_layer"],
                         ids=[e["name"] for e in DECLARED["per_layer"]])
def test_per_layer_reader_loads_and_matches_its_entry(entry):
    reader = common.load_module("layers", entry["name"])
    assert callable(reader.read)
    assert reader.SOURCE == entry["source"]
    assert entry["moves"] in END_TO_END
    assert entry["workloads"] and set(entry["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_names_files_that_exist(cell):
    config = CONFIGS[CELLS[cell]["config"]]
    assert os.path.exists(os.path.join(ROOT, config["file"]))
    traffic = common.load_json("benchmark", "traffic",
                               CELLS[cell]["traffic"] + ".json")
    assert os.path.exists(os.path.join(
        common.BENCH_DIR, "drivers", traffic["driver"] + ".py"))


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_configuration_has_its_file_and_plain_reference(config):
    declared = CONFIGS[config]
    assert common.load_json(declared["file"])
    assert os.path.exists(os.path.join(
        common.BENCH_DIR, "reference", config + ".py"))
    assert any(w["config"] == config for w in CELLS.values())


# -- the documents a newcomer reads first --------------------------------------

FRONT_DOOR = (["README.md", "Makefile", "BASELINE.md", "PARITY.md",
               ".claude/skills/verify/SKILL.md"]
              + sorted(os.path.relpath(p, ROOT) for p in
                       glob.glob(os.path.join(ROOT, "docs", "*.md"))))
# history stays history: CHANGES.md, PERF.md's Findings, ROADMAP.md,
# ADVICE.md and ISSUE.md may name what is gone, and are not read here
# (spelled in halves, so that a grep for the names finds history alone)
GONE = re.compile("|".join([r"bench\.py", "BENCH" + "_r", "MULTICHIP" + "_r"]))
# a `make` target is named by a code span that starts with it, by a line of a
# fenced block that runs it (after `$ ` or `VAR=value `), or by a row of the
# Makefile's own header table; prose ("make sure ...") names none
CODE_SPAN = re.compile(r"`make ([a-z][a-z0-9-]*)")
FENCE = re.compile(r"^```.*?^```", re.M | re.S)
COMMAND = re.compile(
    r"^\s*(?:\$ )?(?:[A-Z_][A-Z0-9_]*=\S+ )*make ([a-z][a-z0-9-]*)", re.M)
HEADER_ROW = re.compile(r"^#\s+make ([a-z][a-z0-9-]*)(?:\s{2,}|$)", re.M)


def _make_targets_named(relative, text):
    if relative == "Makefile":
        return set(HEADER_ROW.findall(text))
    named = set(CODE_SPAN.findall(FENCE.sub("", text)))
    for block in FENCE.findall(text):
        named |= set(COMMAND.findall(block))
    return named


def _read(relative):
    with open(os.path.join(ROOT, relative), encoding="utf-8") as f:
        return f.read()


def test_front_door_names_the_benchmark_that_exists():
    defined = set(re.findall(r"^([a-z][a-z0-9-]*):", _read("Makefile"), re.M))
    assert {"check", "smoke", "cells"} <= defined
    for relative in FRONT_DOOR:
        text = _read(relative)
        assert not GONE.search(text), \
            f"{relative} names {GONE.search(text).group(0)}, which is gone"
        unknown = _make_targets_named(relative, text) - defined
        assert not unknown, \
            f"{relative} names make targets the Makefile lacks: {unknown}"
    readme = _read("README.md")
    assert "benchmark/run.py" in readme and "PERF_LEDGER.jsonl" in readme
    for cell in CELLS:
        assert cell in readme, f"README.md does not name the cell {cell}"
