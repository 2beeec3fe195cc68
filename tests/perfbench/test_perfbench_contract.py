"""The benchmark against its contract, on the CPU: BENCHMARK.json's shape,
``run.py`` refusing to run without a chip, the harness driving each kind of
traffic end to end at a tiny size, and a cell, a configuration, a traffic mix
and a per-layer metric added as new files only."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import pb_tiny

REPO = pb_tiny.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_has_exactly_the_contract_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16 and len(bench["command"]) <= 32
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
    cells_n = len(bench["workloads"])
    # a full check has to fit with the full 24 cells at this run length
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, cells_n // 4)


def test_every_name_unit_and_line_is_within_the_limits(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
    assert len({m["name"] for m in bench["end_to_end"] + bench["per_layer"]}) \
        == len(bench["end_to_end"]) + len(bench["per_layer"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert os.path.isfile(os.path.join(REPO, c["file"]))
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        for line in (c["why"], c["source"]):
            assert 1 <= len(line) <= 200 and "\n" not in line and "\t" not in line
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in bench["workloads"]} == \
        {c["name"] for c in bench["configs"]}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and "\n" not in m["layer"]
        assert 1 <= len(m["layer"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}


def test_every_cell_reports_what_the_contract_asks(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in bench["end_to_end"]}
    assert e2e["setup_s"] == cells
    for cell in cells:
        assert sum(cell in ws for ws in e2e.values()) >= 2, cell
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"]), cell
    for m in bench["per_layer"]:
        # a per-layer metric is read only where its end-to-end metric is
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells and cell in e2e[m["moves"]], (m["name"], cell)
    for ws in e2e.values():
        assert set(ws) <= set(cells)


def test_every_named_thing_has_its_own_file(bench):
    home = os.path.join(REPO, bench["paths"][0])
    for w in bench["workloads"]:
        for rel in (f"traffic/{w['traffic']}.json",
                    f"workloads/{w['name']}.json"):
            assert os.path.isfile(os.path.join(home, rel)), rel
        with open(os.path.join(home, "workloads", w["name"] + ".json")) as f:
            doc = json.load(f)
        assert doc["why"] == w["why"] and doc["who"] and doc["limits"]
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(home, "layer_metrics",
                                           m["name"] + ".py")), m["name"]
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            doc = json.load(f)
        assert doc["source"] == c["source"] and doc["reduced"] == c["reduced"]
        assert doc["assumed"] and doc["family"]
    # files under the benchmark's paths are named from a name's characters
    for p in bench["paths"]:
        for d, dirs, files in os.walk(os.path.join(REPO, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(d, f)


def test_the_entry_names_no_model_traffic_or_metric(bench):
    with open(os.path.join(REPO, "perfbench", "run.py")) as f:
        text = f.read()
    named = ([c["name"] for c in bench["configs"]]
             + [w["traffic"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert not [n for n in named if n in text]


def _run_py(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "bert-base.train.1chip-b32-s512", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_py_exits_nonzero_and_prints_no_result_without_a_tpu():
    out = _run_py(REPO, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert '"metrics"' not in out.stdout


def test_run_py_exits_nonzero_where_only_the_benchmark_is(tmp_path, bench):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_py(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and '"metrics"' not in out.stdout


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    # tiny_tree itself asserts that no file of the benchmark was edited
    return pb_tiny.tiny_tree(tmp_path_factory.mktemp("pb"))


def _listed(bench, group, kind):
    """The metrics of ``group`` that BENCHMARK.json lists for cells of
    ``kind`` (train or serve)."""
    return {m["name"] for m in bench[group]
            if "workloads" not in m
            or any(w.split(".")[1] == kind for w in m["workloads"])}


@pytest.mark.parametrize("workload", ["tiny-enc.train", "tiny-lm.serve"])
def test_a_new_cell_is_found_by_name_and_runs_end_to_end(tree, workload,
                                                         bench, capsys):
    end_to_end = _listed(bench, "end_to_end", workload.split(".")[1])
    assert len(end_to_end) >= 2
    result = pb_tiny.run(tree, workload, seed=2**31 + 11, seconds=0.8)
    assert LAST_LINE_KEYS <= set(result)
    assert result["correct"] is True, capsys.readouterr().out
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == end_to_end
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    json.dumps(result)
    # every number compared is printed beside its limit
    printed = capsys.readouterr().out
    for name, _, _ in result["compared"]:
        assert f"compare {name}:" in printed


@pytest.mark.parametrize("workload, device_trace", [
    ("tiny-enc.train", {"step_ms", "mfu", "sim_error", "flash_share",
                        "flash_roofline", "device_idle_share.train"}),
    ("tiny-lm.serve", {"device_idle_share.serve"})])
def test_a_traced_run_reports_per_layer_metrics_and_a_new_one(
        tree, workload, device_trace, bench):
    kind = workload.split(".")[1]
    some = _listed(bench, "per_layer", kind) - device_trace
    if kind == "train":
        some.add("steps_counted")
    result = pb_tiny.run(tree, workload, seed=7, seconds=1.5, trace=1)
    assert LAST_LINE_KEYS <= set(result) and result["correct"] is True
    # the CPU has no device plane: readers of the device trace return
    # nothing and are left out, the others are there
    assert some <= set(result["metrics"])
    assert "setup_s" not in result["metrics"]


def test_every_seed_is_offered_the_same_work(tree):
    """The sizes of the requests and the label counts of the batches are the
    mix's; the seed draws token ids, weights and which rows carry which label."""
    from perfbench.harness import cells

    serve = cells.load(tree, "tiny-lm.serve")
    driver = serve.module("drivers", serve.traffic["kind"])
    sizes = driver.request_sizes(serve.traffic)
    assert len(sizes) == serve.traffic["size_grid"] and len(set(sizes)) > 4
    a, b = (driver.prompt_tokens(211, seed, 0, sizes[0][0]) for seed in (1, 2))
    assert len(a) == len(b) == sizes[0][0] and (a != b).any()
    train = cells.load(tree, "tiny-enc.train")
    driver = train.module("drivers", train.traffic["kind"])
    fam = train.module("families", train.config["family"])
    feeds = [driver.make_feed(train.traffic, fam.sizes(train.config), seed)
             for seed in (1, 2**31 + 2)]
    for (x1, y1), (x2, y2) in zip(*feeds):
        assert sorted(y1.ravel()) == sorted(y2.ravel())
        assert set(y1.ravel()) == {0, 1} and 8 * y1.sum() == len(y1)
        assert (x1 != x2).any()
    assert any((y1 != y2).any() for (_, y1), (_, y2) in zip(*feeds))


def test_no_tpu_topology_is_described_while_a_module_is_imported():
    for d, dirs, files in os.walk(os.path.join(REPO, "perfbench")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    assert "get_topology_desc" not in fh.read(), f
