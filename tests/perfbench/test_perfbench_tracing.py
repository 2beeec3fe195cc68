"""The readers PR 24 added (the five serving ones listed by PR 32, the
three of the train step in ``data/unlisted_metrics.json``): each against
hand-made spans or a hand-made trace gives the answer worked out by hand and
nothing where there is nothing to read; the tiny cells run traced on the CPU
with them listed; and the serve trace recorded on the chip reduces to its
known answers."""

import json
import os
import types

import pytest

import pb_tiny
import pb_unlisted
from perfbench.harness import cells, step_owners, xtrace
from test_perfbench_trace import _reader

HERE = os.path.dirname(os.path.abspath(__file__))
SERVE = ("queue_ms", "prefill_wait_ms", "prefill_exec_ms", "step_host_ms",
         "decode_device_ms")
TRAIN = ("optimizer_share", "unowned_share", "sim_error_by_op")


def _obs(spans=(), doc=None, counters=None, cell=None):
    return types.SimpleNamespace(
        cell=cell or types.SimpleNamespace(name="t"), counters=counters or {},
        spans=list(spans), trace=doc, xtrace=xtrace,
        window=xtrace.window(doc) if doc else None)


def _span(name, t0_ms, t1_ms, **args):
    s = {"name": name, "t0_ns": int(t0_ms * 1e6), "t1_ns": int(t1_ms * 1e6)}
    if "cat" in args:
        s["cat"] = args.pop("cat")
    if args:
        s["args"] = args
    return s


def _phase(name, step, t0_ms, t1_ms):
    return _span(name, t0_ms, t1_ms, cat="engine", step=step)


def test_the_unlisted_entries_name_readers_that_are_there():
    with open(os.path.join(pb_tiny.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]}
    cells_ = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m.get("workloads", cells_) for m in bench["end_to_end"]}
    entries = pb_unlisted.unlisted()
    assert [m["name"] for m in entries] == list(TRAIN)
    assert set(SERVE) <= listed          # since PR 32
    layers = {m["layer"] for m in bench["per_layer"]}
    for m in entries:
        assert m["name"] not in listed
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["layer"] in layers
        assert set(m["workloads"]) <= set(e2e[m["moves"]])
        assert callable(_reader(m["name"]))


def test_the_span_readers_give_the_answers_worked_out_by_hand(capsys):
    spans = [
        # three requests: queue 10, 20, 60 ms; wait 100, 300, 200; exec 5, 7, 6
        _span("queue", 0, 10, slot=0), _span("prefill_wait", 10, 110),
        _span("prefill_exec", 110, 115, program="jit_prefill", bucket=16),
        _span("queue", 0, 20, slot=1), _span("prefill_wait", 20, 320),
        _span("prefill_exec", 320, 327, program="jit_prefill", bucket=16),
        _span("queue", 0, 60, slot=2), _span("prefill_wait", 60, 260),
        _span("prefill_exec", 260, 266, program="jit_prefill", bucket=32),
        _span("prefill", 10, 115), _span("decode_step", 400, 488, active=3),
        # boundary 1: host-only 1 + 2 + 0.5 + 1.5 + 3 = 8 ms; the dispatch,
        # the fetch and the prefill's own dispatch are not the host's
        _phase("generate.admit", 1, 0, 1),
        _phase("gen-prefill.prepare", 1, 1, 3),
        _phase("gen-prefill", 1, 3, 60),
        _phase("gen-prefill.deliver", 1, 60, 60.5),
        _phase("generate.grow_pages", 1, 60.5, 60.5),
        _phase("generate.prepare", 1, 60.5, 62),
        _phase("generate.dispatch", 1, 62, 64),
        _phase("generate.fetch", 1, 64, 147),
        _phase("generate.deliver", 1, 147, 150),
        # boundary 2: no prefill: 1 + 2 + 3 = 6 ms
        _phase("generate.admit", 2, 150, 151),
        _phase("generate.prepare", 2, 151, 153),
        _phase("generate.fetch", 2, 153, 240),
        _phase("generate.deliver", 2, 240, 243),
        # boundary 3: idle but for the wait for work: 1 ms
        _phase("generate.admit", 3, 243, 244),
        _phase("generate.idle", 3, 244, 294)]
    obs = _obs(spans)
    assert _reader("queue_ms")(obs) == 20.0
    assert _reader("prefill_wait_ms")(obs) == 200.0
    assert _reader("prefill_exec_ms")(obs) == 6.0
    assert _reader("step_host_ms")(obs) == 6.0
    line = capsys.readouterr().out
    assert "over 3 boundaries" in line and "generate.deliver 3.000 (x2)" in line
    assert "generate.fetch" not in line and "generate.idle" not in line
    # the decode program's device time, found by the name the span carries
    doc = {"devices": {0: {"ops": [], "modules": [
        ["jit_decode(17)", 0, 80e6], ["jit_decode(17)", 100e6, 84e6],
        ["jit_decode(17)", 200e6, 82e6], ["jit_decode_s(9)", 300e6, 500e6],
        ["jit_prefill(3)", 900e6, 50e6]]}}, "host": []}
    steps = [_span("decode_step", 0, 88, program="jit_decode", active=3),
             _span("decode_step", 100, 190, program="jit_decode", active=3)]
    assert _reader("decode_device_ms")(_obs(steps, doc)) == 82.0


def test_a_reader_that_finds_nothing_returns_nothing():
    # a program from before PR 24: queue, prefill and decode_step spans
    # without a program, no phases; and a run with no device trace
    old = [_span("queue", 0, 10, slot=0), _span("prefill", 10, 115),
           _span("decode_step", 0, 88, active=3, step=0)]
    doc = {"devices": {0: {"ops": [], "modules": [["jit_decode(1)", 0, 8e7]]}},
           "host": []}
    for name in SERVE[1:]:
        assert _reader(name)(_obs(old, doc)) is None, name
    assert _reader("queue_ms")(_obs(old, doc)) == 10.0
    for name in SERVE:
        assert _reader(name)(_obs()) is None, name
    assert _reader("decode_device_ms")(_obs(
        [_span("decode_step", 0, 88, program="jit_decode")])) is None
    for name in TRAIN:
        assert _reader(name)(_obs(counters={"step_program": "jit_train_step"}
                                  )) is None, name


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """pb_tiny's tree with the unlisted metrics listed for its tiny cells."""
    root = pb_tiny.tiny_tree(tmp_path_factory.mktemp("pb_tracing"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    tiny = {"train": "tiny-enc.train", "serve": "tiny-lm.serve"}
    bench["per_layer"] += [
        dict(m, workloads=[tiny[m["workloads"][0].split(".")[1]]])
        for m in pb_unlisted.unlisted()]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_the_owner_readers_on_a_trace_made_from_the_compiled_step(
        tree, capsys):
    """The tiny encoder's step compiled here gives the table; a hand-made
    trace of two steps runs three of its instructions, one the optimizer's,
    one a graph op's, and one operation the table does not know."""
    cell = cells.load(tree, "tiny-enc.train")
    fam = cell.module("families", cell.config["family"])
    model = fam.build_train(cell.config, cell.traffic, {})
    model.init_layers(seed=0)
    import numpy as np

    table = model.step_op_table(np.zeros((32, 32), np.int32),
                                np.zeros((32, 1), np.int32))
    opt = next(n for n, o in table.items() if o == ("optimizer", None))
    op, phase = "ffn_up_0", "bwd"
    mine = next(n for n, o in table.items() if o == (op, phase))
    doc = {"devices": {0: {
        "ops": [[opt, 1000, 300], [mine, 1300, 100], ["slice-start.9", 1400, 100],
                [opt, 2000, 300], [mine, 2300, 100], ["slice-start.9", 2400, 100],
                [mine, 5000, 999]],          # after the steps: not counted
        "modules": [["jit_train_step(1)", 1000, 600],
                    ["jit_train_step(1)", 2000, 600]]}}, "host": []}
    obs = _obs(doc=doc, counters={"step_program": "jit_train_step"},
               cell=cell)
    got = step_owners.read(obs)
    assert got["steps"] == 2 and got["seconds"] == {
        ("optimizer", None): 600e-9, (op, phase): 200e-9,
        (None, None): 200e-9}
    assert got["by_kind"]["slice-start"] == {"nobody": 200e-9}
    assert got["by_kind"][xtrace.op_kind(mine)] == {"ffn_up.bwd": 200e-9}
    assert step_owners.read(obs) is got          # built once a run
    assert _reader("optimizer_share")(obs) == pytest.approx(60.0)
    assert _reader("unowned_share")(obs) == pytest.approx(20.0)
    line = capsys.readouterr().out
    assert "optimizer 0.0000, ffn_up.bwd 0.0000, nobody 0.0000" in line \
        and "slice-start 0.0000 = nobody 0.0000" in line
    from flexflow_tpu.search.simulator import Simulator

    cfg = got["model"].config
    fwd, bwd = Simulator(
        num_devices=1, flash_attention=cfg.flash_attention,
        compute_dtype=cfg.compute_dtype,
        opt_slot_bytes=got["model"].optimizer.slot_bytes_per_param,
        use_native=False).op_times(got["model"].layers, {})[op]
    # one graph op owns traced time: 100 ns a step against its price
    assert _reader("sim_error_by_op")(obs) == pytest.approx(
        100.0 * abs(fwd + bwd - 100e-9) / 100e-9)


def test_a_cached_step_without_scopes_is_an_error_not_a_guess(
        tree, monkeypatch):
    from flexflow_tpu.model import FFModel

    monkeypatch.setattr(FFModel, "step_op_table",
                        lambda self, *a: {"fusion.1": (None, None)})
    cell = cells.load(tree, "tiny-enc.train")
    doc = {"devices": {0: {"ops": [["fusion.1", 1000, 300]], "modules": [
        ["jit_train_step(1)", 1000, 600]]}}, "host": []}
    with pytest.raises(SystemExit, match="clear the cache"):
        _reader("unowned_share")(_obs(
            doc=doc, counters={"step_program": "jit_train_step"}, cell=cell))


@pytest.mark.parametrize("workload, expect", [
    ("tiny-lm.serve", {"queue_ms", "prefill_wait_ms", "prefill_exec_ms",
                       "step_host_ms"}),
    ("tiny-enc.train", set())])
def test_the_tiny_cells_run_traced_with_the_new_metrics_listed(
        tree, workload, expect, capsys):
    result = pb_tiny.run(tree, workload, seed=11, seconds=1.5, trace=1)
    assert result["correct"] is True, capsys.readouterr().out
    new = set(SERVE + TRAIN)
    # the CPU has no device plane: the span readers report, the readers of
    # the device trace return nothing and are left out
    assert set(result["metrics"]) & new == expect
    if expect:
        m = {k: result["metrics"][k]["value"] for k in expect}
        assert all(v >= 0 for v in m.values())
        assert "step_host_ms over" in capsys.readouterr().out


def test_the_recorded_serve_trace_reduces_to_its_known_answers():
    """Three step boundaries of gpt1.serve.closed-128 on device 0, cut from
    a traced run of the harness on a v5e (PR 24): the decode program's
    device time, the idle share and the idle seconds by the engine's phase
    are recomputed from the file."""
    doc = xtrace.load(os.path.join(HERE, "data",
                                   "recorded_serve_closed128.json"))
    with open(os.path.join(HERE, "data",
                           "recorded_serve_closed128.answers.json")) as f:
        want = json.load(f)
    assert pb_unlisted.serve_answers(xtrace, doc, want["decode_program"]) \
        == want
    steps = [_span("decode_step", 0, 88, program=want["decode_program"])]
    obs = _obs(steps, doc)
    assert _reader("decode_device_ms")(obs) == pytest.approx(
        want["decode_device_ms"], rel=1e-9)
    assert _reader("device_idle_share.serve")(obs) == pytest.approx(
        want["device_idle_share.serve"], rel=1e-9)
    assert 80 < want["decode_device_ms"] < 90
    assert 3 < want["device_idle_share.serve"] < 12
    # the idle gaps fall in phases the engine named; what is left between
    # its annotations is the gap the traced window opens with (before PR 24
    # 96 % of the idle seconds lay between the engine's two annotations)
    idle = dict(want["idle_gaps"])
    assert set(idle) <= {"host:unattributed", "generate.turn", "gen-prefill",
                         "generate.deliver", "generate.prepare",
                         "generate.fetch", "generate.dispatch",
                         "generate.admit", "gen-prefill.prepare",
                         "gen-prefill.deliver", "generate.grow_pages"}
    assert idle["host:unattributed"] < 0.15 * sum(idle.values())
    assert idle["generate.turn"] + idle["gen-prefill"] \
        > 0.8 * sum(idle.values())
    names = {h[0] for h in doc["host"]}
    assert {"generate.admit", "gen-prefill.prepare", "gen-prefill",
            "gen-prefill.deliver", "generate.grow_pages", "generate.prepare",
            "generate", "generate.dispatch", "generate.fetch",
            "generate.deliver", "generate.turn"} <= names
