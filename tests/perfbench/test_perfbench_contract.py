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
                        "flash_roofline", "device_idle_share.train",
                        "collective_exposed_share"}),
    ("tiny-lm.serve", {"device_idle_share.serve", "decode_device_ms",
                       "paged_decode_roofline", "serve_mfu"})])
def test_a_traced_run_reports_per_layer_metrics_and_a_new_one(
        tree, workload, device_trace, bench):
    kind = workload.split(".")[1]
    some = _listed(bench, "per_layer", kind) - device_trace
    if kind == "train":
        some.add("steps_counted")
        if "--budget" not in pb_tiny.TINY_TRAIN["program_args"]:
            some.discard("search_s")    # the mix asks for no search
    result = pb_tiny.run(tree, workload, seed=7, seconds=1.5, trace=1)
    assert LAST_LINE_KEYS <= set(result) and result["correct"] is True
    # the CPU has no device plane: readers of the device trace return
    # nothing and are left out, the others are there
    assert some <= set(result["metrics"])
    assert "setup_s" not in result["metrics"]


def test_the_serve_window_is_printed_by_sub_windows(tree, capsys):
    """The three serve numbers for each sub-window of a run (two at the
    least), as ``pb_sets.py`` reads them back: every token of the window
    falls in exactly one sub-window."""
    import pb_sets

    result = pb_tiny.run(tree, "tiny-lm.serve", seed=2**31 + 5, seconds=1.0)
    printed = capsys.readouterr().out
    subs = [sub for sub in map(pb_sets.parse_subwindow, printed.splitlines())
            if sub]
    assert [(sub["from_s"], sub["to_s"]) for sub in subs] == \
        [(0.0, 0.5), (0.5, 1.0)]
    for sub in subs:
        assert list(sub) == ["from_s", "to_s", "serve_tokens_per_s",
                             "ttft_p95_ms", "itl_p95_ms"]
    assert sum(sub["serve_tokens_per_s"] for sub in subs) / 2 == pytest.approx(
        result["metrics"]["serve_tokens_per_s"]["value"], rel=1e-9)


def test_the_serve_drivers_counts_from_hand_made_client_records():
    """Sub-windows and the traced window's work, from the clients' records
    alone: a stream's token j >= 1 is a decode step over prompt + j
    positions, its token 0 a prefill."""
    import types

    from perfbench.harness import cells

    cell = cells.load(REPO, "gpt1.serve.closed-128")
    driver = cell.module("drivers", cell.traffic["kind"])

    def req(plen, t_submit, t_tokens):
        return types.SimpleNamespace(
            prompt=[0] * plen, want=len(t_tokens), t_submit=t_submit,
            t_tokens=t_tokens, tokens=[1] * len(t_tokens), t_end=t_tokens[-1],
            error=None, cut=False)

    load = types.SimpleNamespace(requests=[
        req(10, 0.0, [1.0, 2.0, 3.0, 4.0]),       # gaps 1, 1, 1
        req(20, 10.5, [12.5, 13.0, 19.0])])       # ttft 2; gaps 0.5, 6
    (a0, b0, first, n0, g0), (a1, b1, second, n1, g1) = \
        driver.subwindows(load, 0.0, 20.0)
    assert (a0, b0, a1, b1) == (0.0, 10.0, 10.0, 20.0)
    assert first == {"serve_tokens_per_s": 0.4, "ttft_p95_ms": 1000.0,
                     "itl_p95_ms": 1000.0} and (n0, g0) == (1, 3)
    assert second == {"serve_tokens_per_s": 0.3, "ttft_p95_ms": 2000.0,
                      "itl_p95_ms": 6000.0} and (n1, g1) == (1, 2)
    assert len(driver.subwindows(load, 0.0, 60.0)) == 6
    assert len(driver.subwindows(load, 0.0, 30.0)) == 3
    # the traced window 1.5-13.5: the first stream's tokens 1, 2, 3 decoded
    # over 11 + 12 + 13 positions; the second's prefill and its token 1 (21)
    work = driver.traced_work(load, 1.5, 13.5)
    assert work == {"decode_tokens": 4,
                    "live_positions": 11 + 12 + 13 + 21, "prompt_lens": [20]}


def _serve_spreads():
    with open(os.path.join(REPO, "tests", "perfbench", "data",
                           "serve_spreads.json")) as f:
        return json.load(f)


def _widest_recorded(doc, metric, seconds):
    """The widest spread on record for ``metric``: the driver's own readings
    and the builder's sets at the benchmark's run length, each set's spread
    taken as the check takes it."""
    from perfbench.harness.stats import spread_less_farthest

    spreads = [r["spread"][metric] for r in doc["driver_readings"]]
    spreads += [spread_less_farthest(s["runs"][metric])
                for s in doc["sets"] if s["seconds"] == seconds]
    return max(spreads)


def test_the_spread_is_taken_as_the_check_takes_it():
    from perfbench.harness.stats import (spread_less_farthest,
                                         spread_quartiles)

    runs = [100.0, 101.0, 102.0, 103.0, 110.0, 99.0]
    # median 101.5; 110 is the farthest; 99-103 is left
    assert spread_less_farthest(runs) == pytest.approx(4 / 101.5)
    assert spread_less_farthest([90.0, 100.0, 101.0]) == pytest.approx(0.01)
    # quartiles of six: 99.75 and 104.75
    assert spread_quartiles(runs) == pytest.approx(5 / 101.5)
    with pytest.raises(ValueError):
        spread_less_farthest([1.0, 2.0])


SERVE_METRICS = ("serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms")


def _rule_holds(bound, widest):
    return 2.5 * widest <= bound <= 0.10


@pytest.mark.parametrize("metric", SERVE_METRICS)
def test_a_serve_bound_is_twice_the_widest_recorded_spread_or_more(
        bench, metric):
    """PERF.md section 2's rule, held against the recorded runs: a bound is
    two and a half times the widest spread on record at the benchmark's run
    length, rounded up to half a percent.  The check refuses a bound whose
    own runs spread by more than half of it, so twice is the edge (ISSUE 32)
    and the half more is the room for a fresh set that reads wider than any
    on record; a metric that needs more than 10 % is no gate."""
    doc = _serve_spreads()
    serve = [w["name"] for w in bench["workloads"]
             if w["name"] == doc["workload"]]
    entry = next(m for m in bench["end_to_end"] if m["name"] == metric)
    assert entry["workloads"] == serve
    widest = _widest_recorded(doc, metric, bench["run_seconds"])
    assert widest > 0
    assert _rule_holds(entry["bound"], widest), (metric, widest)
    # to half a percent
    assert round(entry["bound"] * 200) == pytest.approx(entry["bound"] * 200)
    assert entry["bound"] - 2.5 * widest < 0.005 + 1e-12, "rounded up, no more"
    # the rule fails a bound set under twice a recorded spread, or over 10 %
    assert not _rule_holds(2 * widest, widest)
    assert not _rule_holds(0.105, widest)


def test_the_recorded_sets_are_whole_and_say_where_they_come_from(bench):
    doc = _serve_spreads()
    assert len(doc["driver_readings"]) >= 1
    for r in doc["driver_readings"]:
        assert r["origin"] and set(r["spread"]) == set(SERVE_METRICS)
    at_length = [s for s in doc["sets"] if s["seconds"] == bench["run_seconds"]]
    assert len(at_length) >= 3
    assert len([s for s in doc["sets"]
                if s["seconds"] != bench["run_seconds"]]) >= 2
    for s in doc["sets"]:
        assert s["origin"] and len(s["seeds"]) >= 6
        for metric in SERVE_METRICS:
            assert len(s["runs"][metric]) == len(s["seeds"])
            assert all(v > 0 for v in s["runs"][metric])
    # the other bounds stand as they were
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    assert bounds["train_tokens_per_s"] == 0.01 and bounds["setup_s"] == 0.1


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
