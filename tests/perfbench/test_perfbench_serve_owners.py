"""The serving programs' device time by graph op (ISSUES 38, 39): the
reduction ``perfbench/harness/serve_owners.py`` and the three readers it
feeds, against a hand-made trace whose answers are worked out by hand; the
owner tables of the tiny serve cells, had from graphs with no weights
installed; what a reader says where there is nothing to read (nothing) and
of a program without the counter (100 / 0, its chunks by the one name its
spans carry); a cached program without scopes as an error; the three entries
as ``BENCHMARK.json`` lists them."""

import os
import types

import pytest

import pb_tiny
from perfbench.harness import cells, serve_owners, xtrace
from test_perfbench_trace import _reader

NEW = ("chunk_device_ms", "serve_unowned_share", "attention_share.serve")
SERVE_CELLS = ["gpt1.serve.closed-128", "laguna-xs2.serve.closed-128-code"]
SERVE_PRESETS = [p["cell"]["name"] for p in pb_tiny.presets()
                 if p["traffic"]["kind"] == "serve_closed"]

# `fusion.1` is the head's in the 16-token chunk program and an attention
# op's in the token step; the splice has no table here, `copy.9` no entry
TABLES = {
    "jit_prefill_16": {"fusion.1": ("lm_head", None),
                       "fusion.2": ("attention_0", None),
                       "ragged.3": ("moe_1", "moe_experts"),
                       "argmax.4": ("sample", None)},
    "jit_decode": {"fusion.1": ("attention_1", None),
                   "paged.5": ("attention_0", None),
                   "ragged.3": ("moe_1", "moe_experts"),
                   "sort.6": ("moe_1", None)}}
# the window is 1 000 .. 9 000 ns; a token step is under way when it opens
# (its event starts before it) and a chunk program when it closes
TRACE = {"devices": {0: {
    "modules": [["jit_decode(7)", 500, 1000],
                ["jit_prefill_16(5)", 2000, 1000],
                ["jit_splice_tokens(1)", 3100, 100],
                ["jit_decode(7)", 4000, 600],
                ["jit_prefill_16(5)", 5000, 1200],
                ["jit_decode(7)", 7000, 800],
                ["jit_prefill_16(5)", 8500, 1000]],
    "ops": [["fusion.1", 600, 800],           # in a program the window cuts
            ["fusion.1", 2000, 300], ["fusion.2", 2300, 200],
            ["ragged.3", 2500, 400], ["argmax.4", 2900, 50],
            ["copy.9", 2950, 50],             # the table lacks it: nobody's
            ["fusion.1", 3100, 100],          # the splice: no table at all
            ["copy.9", 3500, 200],            # between programs: nowhere
            ["fusion.1", 4000, 100], ["paged.5", 4100, 300],
            ["ragged.3", 4400, 150], ["sort.6", 4550, 50],
            ["fusion.1", 5000, 500], ["ragged.3", 5500, 700],
            ["fusion.1", 7000, 200], ["paged.5", 7200, 500],
            ["sort.6", 7700, 100],
            ["fusion.1", 8500, 400],          # its program ends after it
            ["fusion.1", 9500, 100]]}},       # after the window
    "host": [["pb.traced_window", 1000, 8000]]}
NS = 1e-9


def _obs(spans=(), doc=TRACE, tables=TABLES):
    obs = types.SimpleNamespace(
        cell=types.SimpleNamespace(name="t"), counters={}, spans=list(spans),
        trace=doc, xtrace=xtrace, window=xtrace.window(doc) if doc else None)
    if doc is not None:
        obs._serve_owners = serve_owners.reduce(doc, obs.window, tables,
                                                xtrace)
    return obs


def _chunk(program, **args):
    return {"name": "gen-prefill", "cat": "engine", "t0_ns": 0, "t1_ns": 1,
            "args": dict(args, step=1, program=program)}


def test_a_hand_made_trace_reduces_to_the_answers_worked_out_by_hand():
    got = serve_owners.reduce(TRACE, (1000, 9000), TABLES, xtrace)
    assert {k: round(v / NS) for k, v in got["seconds"].items()} == {
        ("chunk", "lm_head", None): 800,                # 300 + 500
        ("chunk", "attention", None): 200,
        ("chunk", "moe", "moe_experts"): 1100,          # 400 + 700
        ("chunk", "sample", None): 50,
        ("chunk", "nobody", None): 50,
        ("other", "nobody", None): 100,
        ("token", "attention", None): 1100,    # fusion.1 300 + paged.5 800
        ("token", "moe", "moe_experts"): 150,
        ("token", "moe", None): 150}
    assert round(got["total"] / NS) == 3700
    # the owners' seconds are the programs' seconds, far under a microsecond
    assert abs(sum(got["seconds"].values()) - got["total"]) < 1e-12
    assert round(got["unowned"] / NS) == 150
    assert got["programs"] == {"jit_prefill_16": [2, pytest.approx(1e-3)],
                               "jit_splice_tokens": [1, pytest.approx(1e-4)],
                               "jit_decode": [2, pytest.approx(6e-4)]}
    # one name, two owners: whose `fusion` is depends on the program
    assert {k: round(v / NS) for k, v in got["by_kind"]["fusion"].items()} \
        == {"chunk:lm_head": 800, "chunk:attention": 200,
            "token:attention": 300, "other:nobody": 100}
    assert [serve_owners.program_kind(p) for p in (
        "jit_prefill_512", "jit_decode", "jit_decode_s", "jit_verify_4",
        "jit_splice_tokens")] == [
        "chunk", "token", "token", "other", "other"]


def test_the_three_readers_give_the_hands_numbers(capsys):
    obs = _obs([_chunk("jit_prefill_16", chunk=0, length=9)])
    assert _reader("serve_unowned_share")(obs) == pytest.approx(
        100.0 * 150 / 3700)
    out = capsys.readouterr().out
    assert "jit_decode x2 0.001, jit_prefill_16 x2 0.001" in out
    assert "by owner, ms a chunk program over 2" in out \
        and "by owner, ms a token program over 2" in out
    assert "fusion 0.0000 = chunk:lm_head 0.0000 + token:attention" in out
    # operations wholly inside the window: the 3 700 inside whole programs,
    # 200 between programs, 400 of the chunk the window cuts
    assert xtrace.busy_seconds(TRACE, (1000, 9000)) == pytest.approx(4300 * NS)
    assert _reader("attention_share.serve")(obs) == pytest.approx(
        100.0 * (200 + 1100) / 4300)
    # the two whole chunk programs: 1 000 and 1 200 ns, the lower median
    assert _reader("chunk_device_ms")(obs) == pytest.approx(1e-3)
    assert "jit_prefill_16 x2" in capsys.readouterr().out


def test_a_reader_with_nothing_to_read_returns_nothing():
    # no device trace (a CPU rehearsal): none of the three reads, and the
    # reduction is not even built
    cpu = _obs([_chunk("jit_prefill_16")], doc=None)
    assert serve_owners.read(cpu) is None
    for name in NEW:
        assert _reader(name)(cpu) is None, name
    # a trace whose window holds no whole program
    empty = {"devices": {0: {"modules": [], "ops": [["fusion.1", 1500, 10]]}},
             "host": [["pb.traced_window", 1000, 8000]]}
    none = _obs([_chunk("jit_prefill_16")], doc=empty)
    assert _reader("serve_unowned_share")(none) is None
    assert _reader("chunk_device_ms")(none) is None
    # chunk programs in the trace that no span names
    assert _reader("chunk_device_ms")(_obs()) is None


def test_a_program_without_the_counter_reads_100_and_0():
    """A program whose chunk buckets share one name, named on no
    ``gen-prefill`` span, with no table (ISSUE 39's parent, which the driver
    runs traced under this benchmark): every second inside its programs is
    nobody's, so ``serve_unowned_share`` reads 100 and
    ``attention_share.serve`` 0, as their definitions say, and
    ``chunk_device_ms`` finds the chunks by the one name the request's
    ``prefill_exec`` spans carry."""
    doc = {"devices": {0: {
        "modules": [[n.replace("_16", ""), s, d]
                    for n, s, d in TRACE["devices"][0]["modules"]],
        "ops": TRACE["devices"][0]["ops"]}}, "host": TRACE["host"]}
    spans = [{"name": "gen-prefill", "cat": "engine", "t0_ns": 0, "t1_ns": 1,
              "args": {"step": 1, "chunk": 0, "length": 9}},
             {"name": "prefill_exec", "t0_ns": 0, "t1_ns": 1,
              "args": {"program": "jit_prefill", "bucket": 16}}]
    obs = _obs(spans, doc, tables={})
    assert {k: round(v / NS) for k, v in
            obs._serve_owners["seconds"].items()} == {
        ("chunk", "nobody", None): 2200,
        ("other", "nobody", None): 100,
        ("token", "nobody", None): 1400}
    assert _reader("serve_unowned_share")(obs) == 100.0
    assert _reader("attention_share.serve")(obs) == 0.0
    assert _reader("chunk_device_ms")(obs) == pytest.approx(1e-3)
    # tables that give attention nothing read 0 as well
    renamed = {p: {i: (("mixer_0", part) if owner and owner.startswith(
                   "attention") else (owner, part))
                   for i, (owner, part) in t.items()}
               for p, t in TABLES.items()}
    assert _reader("attention_share.serve")(_obs(tables=renamed)) == 0.0


def test_a_program_without_the_method_has_no_tables(monkeypatch, capsys):
    """The parent of the PR that brought the counter: ``tables`` answers
    ``{}`` at once, without building the cell's graph."""
    from flexflow_tpu.serving.generation.decoder import GraphDecoder

    monkeypatch.delattr(GraphDecoder, "program_op_tables")
    cell = types.SimpleNamespace(name="t")      # nothing of it is read
    assert serve_owners.tables(cell) == {}
    assert "no owner tables" in capsys.readouterr().out


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return pb_tiny.tiny_tree(tmp_path_factory.mktemp("pb_serve_owners"))


@pytest.mark.parametrize("preset", SERVE_PRESETS)
def test_a_cells_tables_are_had_without_weights_or_a_pool(tree, preset,
                                                           capsys):
    """The tiny serve cells' graphs built through their families with
    nothing installed: a table for every program the engine's warm-up
    builds, under the names a trace prints; every one names attention ops;
    the sparse family's carry the experts' part."""
    cell = cells.load(tree, preset)
    assert preset in SERVE_PRESETS and len(SERVE_PRESETS) == 2
    got = serve_owners.tables(cell)
    assert "owner tables of" in capsys.readouterr().out
    chunks = [n for n in got if serve_owners.program_kind(n) == "chunk"]
    assert len(chunks) >= 3 and len(set(chunks)) == len(chunks)
    assert set(got) == {*chunks, "jit_decode", "jit_splice_tokens"}
    for name in (*chunks, "jit_decode"):
        owners = set(got[name].values())
        assert any(o and o.startswith("attention_") for o, _ in owners), name
        assert ("sample", None) in owners, name
        if cell.config["family"] == "laguna":
            assert any(part == "moe_experts" for _, part in owners), name
    assert set(got["jit_splice_tokens"].values()) >= {("step_io", None)}


def test_a_cached_program_without_scopes_is_an_error_not_a_guess(
        tree, monkeypatch):
    from flexflow_tpu.serving.generation import decoder

    monkeypatch.setattr(decoder, "table_from_hlo",
                        lambda text, owners, parts: {"fusion.1": (None, None)})
    with pytest.raises(SystemExit, match="clear the cache"):
        serve_owners.tables(cells.load(tree, SERVE_PRESETS[0]))


def test_the_three_entries_are_listed_last_for_both_serve_cells():
    """Appended at the END of ``per_layer`` (an entry put in the middle reads
    as a change to what was there), for both serve cells and no other, each
    with its reader."""
    bench = pb_tiny._json(os.path.join(pb_tiny.REPO, "BENCHMARK.json"))
    assert SERVE_CELLS == [w["name"] for w in bench["workloads"]
                           if ".serve." in w["name"]]
    mine = {m["name"]: m for m in bench["per_layer"][-3:]}
    assert list(mine) == list(NEW)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in mine.values():
        assert m["workloads"] == SERVE_CELLS and m["source"] == "device_trace"
        assert m["moves"] in e2e
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.isfile(os.path.join(
            pb_tiny.REPO, "perfbench", "layer_metrics", m["name"] + ".py"))
    assert mine["chunk_device_ms"]["moves"] == "ttft_p95_ms"
    assert {mine[n]["moves"] for n in NEW[1:]} == {"serve_tokens_per_s"}
