"""Host phases and device-op ownership (ISSUE 24): the generation engine's
step-boundary phases under a fake clock, synchronous (the fleet's entry) and
one step ahead (the engine's own loop, ISSUE 34), the request's road to its
first token tiled by three spans, tracing off as a no-op, and the compiled
train step's instructions mapped to the graph ops that own them.
"""

import os
import sys

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.obs.device_ops import (STEP_OWNERS, attribute,
                                         table_from_hlo)
from flexflow_tpu.obs.trace import get_tracer
from flexflow_tpu.parallel.mesh import MachineMesh
from flexflow_tpu.serving.generation import GenerationEngine

sys.path.insert(0, os.path.dirname(__file__))
try:
    from test_generation import _build_lm
finally:
    sys.path.pop(0)

# a boundary with nothing left in flight (ISSUE 34): the join's chunk and the
# token step are dispatched, then ONE fetch, then both hand-overs
BOUNDARY = ["generate.admit", "gen-prefill.prepare", "gen-prefill",
            "generate.grow_pages", "generate.prepare", "generate.dispatch",
            "generate.fetch", "gen-prefill.deliver", "generate.deliver",
            "generate.turn"]


class TickClock:
    """Every read is a millisecond later than the last: spans recorded on
    it have distinct ends, and nothing depends on how long the CPU took."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.001
        return self.t


@pytest.fixture(scope="module")
def lm():
    return _build_lm()


@pytest.fixture
def tracer():
    tr = get_tracer()
    tr.reset()
    tr.configure(sample_rate=1.0)
    yield tr
    tr.disable()
    tr.reset()


def _drive(lm, prompts, chunk=0, max_new=4, ahead=False):
    """Serve ``prompts`` under a TickClock, one boundary at a time on the
    calling thread (the fleet's entry, ``dispatch_pending``) or, ``ahead``,
    through the engine's own loop, every prompt queued before it starts
    (so that one thread reads the clock); returns the engine, each stream's
    tokens and what each ``dispatch_pending`` charged."""
    eng = GenerationEngine(lm, slots=2, max_new_tokens=max_new,
                           prefill_chunk=chunk, prefix_cache="off",
                           clock=TickClock())
    charged = []
    if ahead:
        streams = [eng.submit(np.asarray(p, np.int32)) for p in prompts]
        eng.start(warmup=False)
    else:
        eng.begin_external_dispatch(warmup=False)
        streams = [eng.submit(np.asarray(p, np.int32)) for p in prompts]
        for _ in range(200):
            if not eng.has_pending:
                break
            charged.append(eng.dispatch_pending())
            assert eng._inflight is None and not eng._cur
    tokens = [list(s.result(timeout=60)) for s in streams]
    eng.stop()
    return eng, tokens, charged


def _by_name(spans, name):
    return [s for s in spans if s["name"] == name]


def test_one_boundary_records_each_phase_once_in_order(lm, tracer):
    _drive(lm, [[5, 6, 7]])
    spans = tracer.snapshot()["spans"]
    engine = [s for s in spans if s.get("cat") == "engine"]
    first = [s for s in engine if s["args"]["step"] == 1]
    # the join's boundary does everything: admits, prefills, decodes once
    assert [s["name"] for s in sorted(first, key=lambda s: s["t0_ns"])] \
        == BOUNDARY
    ordered = sorted(first, key=lambda s: s["t0_ns"])
    for a, b in zip(ordered, ordered[1:]):
        assert a["t1_ns"] <= b["t0_ns"], (a["name"], b["name"])
    # later boundaries have no prefill left and share their own number
    second = {s["name"] for s in engine if s["args"]["step"] == 2}
    assert second == set(BOUNDARY) - {"gen-prefill.prepare", "gen-prefill",
                                      "gen-prefill.deliver"}
    # the dispatch and the fetch lie inside the step's decode_step span
    step = _by_name(spans, "decode_step")[0]
    assert step["args"]["program"] == "jit_decode"
    for name in ("generate.dispatch", "generate.fetch"):
        inner = _by_name(first, name)[0]
        assert step["t0_ns"] <= inner["t0_ns"] <= inner["t1_ns"] \
            <= step["t1_ns"]


def _boundaries(spans):
    """The engine's phase spans by the boundary (``step``) they share, each
    boundary's in time order; every phase at most once and none overlapping
    the next."""
    by = {}
    for s in spans:
        if s.get("cat") == "engine":
            by.setdefault(s["args"]["step"], []).append(s)
    for step, phases in by.items():
        phases.sort(key=lambda s: s["t0_ns"])
        names = [s["name"] for s in phases]
        assert len(names) == len(set(names)), (step, names)
        for a, b in zip(phases, phases[1:]):
            assert a["t1_ns"] <= b["t0_ns"], (step, a["name"], b["name"])
    assert sorted(by) == list(range(1, len(by) + 1))
    return by


def test_dispatch_pending_leaves_nothing_in_flight_and_charges_its_step(
        lm, tracer):
    _, tokens, charged = _drive(lm, [[5, 6, 7]], max_new=4)
    assert len(tokens[0]) == 4
    spans = tracer.snapshot()["spans"]
    by = _boundaries(spans)
    steps = _by_name(spans, "decode_step")
    assert len(steps) == 3 == len(by) and len(charged) == 3
    for k, (step, dt) in enumerate(zip(steps, charged), 1):
        # the step's dispatch AND its fetch lie in the boundary that ran
        # it, and the seconds charged cover it from end to end
        names = {s["name"]: s for s in by[k]}
        assert step["t0_ns"] <= names["generate.dispatch"]["t0_ns"]
        assert names["generate.fetch"]["t1_ns"] <= step["t1_ns"]
        assert dt >= (step["t1_ns"] - step["t0_ns"]) / 1e9 > 0


def test_one_step_ahead_each_boundary_keeps_its_phases_once(lm, tracer):
    """The engine's own loop: one ``step`` a boundary and each phase once
    in it, but a token step's fetch lies in the boundary AFTER its
    dispatch, behind the next step's dispatch; its ``decode_step`` span
    still runs from its dispatch to its tokens on the host, so consecutive
    spans overlap, and the join's ``prefill_exec`` ends at that same
    fetch."""
    _, tokens, _ = _drive(lm, [[5, 6, 7], list(range(1, 11))], max_new=6,
                          ahead=True)
    assert [len(t) for t in tokens] == [6, 6]
    spans = tracer.snapshot()["spans"]
    by = _boundaries(spans)
    steps = sorted(_by_name(spans, "decode_step"),
                   key=lambda s: s["args"]["step"])
    assert [s["args"]["step"] for s in steps] == list(range(len(steps)))
    ran_ahead = 0
    for step in steps:
        inside = [(b, s) for b, phases in by.items() for s in phases
                  if s["name"] in ("generate.dispatch", "generate.fetch")
                  and step["t0_ns"] <= s["t0_ns"]
                  and s["t1_ns"] <= step["t1_ns"]]
        b_dispatch = min(b for b, s in inside
                         if s["name"] == "generate.dispatch")
        b_fetch = max(b for b, s in inside if s["name"] == "generate.fetch")
        # never fetched in the boundary that dispatched it; where no step
        # followed (the batch ran empty) the next boundary fetches at once
        assert b_fetch == b_dispatch + 1
        order = [s["name"] for s in by[b_fetch]]
        if "generate.dispatch" in order:
            ran_ahead += 1
            assert order.index("generate.dispatch") \
                < order.index("generate.fetch") \
                < order.index("generate.deliver")
    assert ran_ahead == len(steps) - 1
    for a, b in zip(steps, steps[1:]):
        if b["args"]["step"] <= ran_ahead:
            assert b["t0_ns"] < a["t1_ns"]       # one step in flight
    # the first tokens came with a step's tokens, in one fetch
    fetches = [s for phases in by.values() for s in phases
               if s["name"] == "generate.fetch"]
    for x in _by_name(spans, "prefill_exec"):
        assert any(f["t1_ns"] <= x["t1_ns"] <= f["t1_ns"] + 2_000_000
                   for f in fetches)


@pytest.mark.parametrize("ahead", [False, True])
@pytest.mark.parametrize("chunk, chunks", [(0, 1), (4, 3)])
def test_queue_wait_and_exec_tile_submit_to_first_token(lm, tracer, chunk,
                                                        chunks, ahead):
    _drive(lm, [list(range(1, 11)), [9, 8, 7]], chunk=chunk, ahead=ahead)
    spans = tracer.snapshot()["spans"]
    requests = _by_name(spans, "request")
    assert len(requests) == 2
    for req in requests:
        mine = {s["name"]: s for s in spans if s.get("trace") == req["trace"]
                and s["name"] != "request"}
        assert set(mine) == {"queue", "prefill", "prefill_wait",
                             "prefill_exec"}
        q, w, x, p = (mine[n] for n in ("queue", "prefill_wait",
                                        "prefill_exec", "prefill"))
        # to the nanosecond: each span starts where the last one ended
        assert q["t0_ns"] == req["t0_ns"]
        assert q["t1_ns"] == w["t0_ns"] == p["t0_ns"]
        assert w["t1_ns"] == x["t0_ns"] and x["t1_ns"] == p["t1_ns"]
        assert w["t0_ns"] < w["t1_ns"] < x["t1_ns"]
        assert x["args"]["program"].startswith("jit_prefill")
        assert x["args"]["bucket"] >= 1 and x["args"]["step"] >= 1
    long = next(s for s in _by_name(spans, "prefill_exec")
                if s["args"]["prompt_len"] == 10)
    assert long["args"]["prefill_chunks"] == chunks
    # a chunked prompt's exec spans the boundaries between its chunks: it
    # holds that many gen-prefill phases, the first starting with it
    held = [s for s in _by_name(spans, "gen-prefill")
            if long["t0_ns"] <= s["t0_ns"] and s["t1_ns"] <= long["t1_ns"]]
    assert len(held) >= chunks


def test_tracing_off_records_nothing_and_serves_the_same_tokens(lm):
    tr = get_tracer()
    tr.disable()
    tr.reset()
    prompts = [[5, 6, 7], list(range(1, 11))]
    eng, off, _ = _drive(lm, prompts, chunk=4)
    assert tr.snapshot()["spans"] == []
    tr.configure(sample_rate=1.0)
    try:
        _, on, _ = _drive(lm, prompts, chunk=4)
        assert tr.snapshot()["spans"]
    finally:
        tr.disable()
        tr.reset()
    assert on == off


def test_each_chunk_phase_names_the_program_it_dispatched(lm, tracer):
    """Two chunk buckets are two programs with two names (``XLA Modules``
    tells them apart), and every ``gen-prefill`` phase span carries the
    name of the one it dispatched and its bucket: a 10-token prompt in
    chunks of 4 runs the 4-token program twice and the 2-token one once;
    the request's ``prefill_exec`` names its last."""
    eng, _, _ = _drive(lm, [list(range(1, 11))], chunk=4)
    spans = tracer.snapshot()["spans"]
    chunks = sorted(_by_name(spans, "gen-prefill"), key=lambda s: s["t0_ns"])
    assert [(s["args"]["chunk"], s["args"]["length"], s["args"]["bucket"],
             s["args"]["program"]) for s in chunks] == [
        (0, 4, 4, "jit_prefill_4"), (1, 4, 4, "jit_prefill_4"),
        (2, 2, 2, "jit_prefill_2")]
    dec = eng._decoder
    names = {b: "jit_" + dec.prefill_fn(b).__name__ for b in (2, 4)}
    assert names == {2: "jit_prefill_2", 4: "jit_prefill_4"}
    last, = _by_name(spans, "prefill_exec")
    assert (last["args"]["program"], last["args"]["bucket"]) \
        == ("jit_prefill_2", 2)


# ----------------------------------------------------------------------
# device operations by graph op
# ----------------------------------------------------------------------
def _two_layer():
    cfg = ff.FFConfig(batch_size=8, compute_dtype="float32")
    m = ff.FFModel(cfg, mesh=MachineMesh({"n": 1}))
    x = m.create_tensor((8, 12), name="x")
    t = m.dense(x, 16, activation="relu", name="hidden")
    t = m.dense(t, 5, name="head")
    m.compile(ff.AdamOptimizer(alpha=1e-3),
              ff.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.METRICS_ACCURACY])
    m.init_layers(seed=0)
    return m


def test_every_graph_op_owns_instructions_of_the_compiled_step():
    m = _two_layer()
    x = np.zeros((8, 12), np.float32)
    y = np.zeros((8, 1), np.int32)
    table = m.step_op_table(x, y)
    owners = set(table.values())
    for op in ("hidden", "head"):        # ops with weights: both passes
        assert {(op, "fwd"), (op, "bwd")} <= owners
    assert ("optimizer", None) in owners
    assert any(o == "loss" for o, _ in owners)
    assert {o for o, _ in owners} <= {"hidden", "head", None, *STEP_OWNERS}
    # asked again for the same shapes, the table is the one kept
    assert m.step_op_table(x, y) is table
    # and the step still trains under its scopes
    assert np.isfinite(float(m.train_batch(x, y)))


# as the TPU compiler names a Pallas kernel's custom call (compiled for a
# described v5e, PR 24): after the kernel, with an instance number
PALLAS_HLO = '''
HloModule jit_train_step
%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%p), metadata={op_name="jit(train_step)/jvp(ffn_up_0)/neg"}
}
ENTRY %main.4 (q.1: bf16[4,12,512,64]) -> bf16[4,12,512,64] {
  %q.1 = bf16[4,12,512,64]{3,2,1,0} parameter(0), metadata={op_name="params['attention_0/wq']"}
  %copy.33 = bf16[4,12,512,64]{3,2,1,0} copy(%q.1), metadata={op_name="params['attention_0/wq']"}
  %flash_attention.2 = bf16[4,12,512,64]{3,2,1,0} custom-call(%copy.33), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(train_step)/jvp(attention_0)/jit(flash_attention)/pallas_call" stack_frame_id=5}, backend_config={"custom_call_config": {"body": "TUzvUgFN"}}
  %flash_mha_bwd_dkv_block_q_major_512_block_q_512.4 = bf16[4,12,512,64]{3,2,1,0} custom-call(%flash_attention.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(attention_0))/jit(flash_attention)/flash_mha_bwd_dkv_block_q_major=512_block_q=512/pallas_call" stack_frame_id=5}
  %fusion.7 = f32[8]{0} fusion(%flash_attention.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(train_step)/jvp(ffn_up_0)/neg;jit(train_step)/jvp(ffn_up_0)/mul"}
  %transpose.3 = f32[8]{0} transpose(%fusion.7), dimensions={0}, metadata={op_name="jit(train_step)/optimizer/transpose"}
  %slice-start.1 = f32[8]{0} slice-start(%transpose.3)
  ROOT %add.9 = bf16[4,12,512,64]{3,2,1,0} add(%copy.33, %copy.33), metadata={op_name="jit(train_step)/jit(_where)/select_n"}
}
'''


def test_a_pallas_custom_call_maps_by_its_kernels_name():
    table = table_from_hlo(PALLAS_HLO, ["attention_0", "ffn_up_0",
                                        *STEP_OWNERS])
    assert table["flash_attention.2"] == ("attention_0", "fwd")
    assert table["flash_mha_bwd_dkv_block_q_major_512_block_q_512.4"] \
        == ("attention_0", "bwd")
    assert table["fusion.7"] == ("ffn_up_0", "fwd")
    # the primitive called transpose is no backward pass
    assert table["transpose.3"] == ("optimizer", None)
    # a copy XLA made of a parameter names the parameter, not a scope; a
    # stack with no scope of ours is nobody's; neither is guessed
    assert table["copy.33"] == table["add.9"] == (None, None)
    assert "slice-start.1" not in table
    ops = [["flash_attention.2", 0, 3e9], ["fusion.7", 0, 1e9],
           ["fusion.7", 5, 1e9], ["slice-start.1", 0, 5e8],
           ["copy.33", 0, 25e7]]
    assert attribute(ops, table) == {("attention_0", "fwd"): 3.0,
                                     ("ffn_up_0", "fwd"): 2.0,
                                     (None, None): 0.75}
