"""The reduction from a profiler trace to per-layer metrics, on a synthetic
trace whose answers can be worked out by hand and on a small trace recorded
on the chip (PR 23), and the benchmark's own FLOP count."""

import json
import os
import types

import pytest

import pb_tiny
from perfbench.harness import cells, xtrace

HERE = os.path.dirname(os.path.abspath(__file__))


FLASH = types.SimpleNamespace(FLASH_KERNELS=r"^(flash_mha_|flash_attention)")


def _obs(doc, counters, traffic=None, peaks=None, sizes=None, flops=FLASH):
    cell = types.SimpleNamespace(traffic=traffic or {"seq_len": 512})
    return types.SimpleNamespace(
        cell=cell, counters=counters, spans=[], trace=doc,
        window=xtrace.window(doc), peaks=peaks or {}, sizes=sizes or {},
        flops=flops, xtrace=xtrace)


def _reader(name):
    cell = cells.Cell(root=pb_tiny.REPO,
                      home=os.path.join(pb_tiny.REPO, "perfbench"), name="t",
                      chips=1, config_name="", config={}, traffic_name="",
                      traffic={}, doc={}, end_to_end=[], per_layer=[],
                      run_seconds=1)
    return cell.module("layer_metrics", name).read


SYNTHETIC = {
    "devices": {0: {
        "ops": [["fusion.1", 1000, 400], ["flash_mha_fwd.2", 1400, 200],
                ["all-reduce.3", 1600, 100], ["copy.4", 1800, 100],
                ["fusion.1", 2000, 400], ["flash_mha_fwd.2", 2400, 200],
                ["all-reduce.3", 2600, 100], ["copy.4", 2800, 100]],
        "modules": [["jit_train_step(1)", 1000, 900],
                    ["jit_train_step(1)", 2000, 900]]}},
    "host": [["pb.traced_window", 900, 2100],
             ["pb.train.wait_two_back", 1650, 400],
             ["pb.train.dispatch", 2650, 200]]}


def test_the_synthetic_trace_reduces_to_the_answers_worked_out_by_hand():
    doc = SYNTHETIC
    win = xtrace.window(doc)
    assert win == (900, 3000)
    busy_s, window_s, gaps = xtrace.busy(doc, win)
    assert busy_s == pytest.approx(1600e-9) and window_s == pytest.approx(2100e-9)
    assert gaps == [(900, 1000), (1700, 1800), (1900, 2000), (2700, 2800),
                    (2900, 3000)]
    counters = {"step_program": "jit_train_step", "chips": 1,
                "tokens_per_step": 1024}
    obs = _obs(doc, counters)
    assert _reader("step_ms")(obs) == pytest.approx(900e-6)
    assert _reader("device_idle_share.train")(obs) == pytest.approx(
        100 * (1 - 1600 / 2100))
    assert _reader("flash_share")(obs) == pytest.approx(100 * 400 / 1600)
    assert _reader("collective_exposed_share")(obs) == pytest.approx(
        100 * 200 / 2100)
    assert xtrace.top_ops(doc)[:2] == [["fusion", pytest.approx(800e-9)],
                                       ["flash_mha_fwd", pytest.approx(400e-9)]]
    # each gap goes to the innermost host span over its middle
    by = dict(xtrace.idle_gaps(doc, gaps))
    assert by["pb.train.wait_two_back"] == pytest.approx(200e-9)
    assert by["pb.train.dispatch"] == pytest.approx(100e-9)
    assert by["host:unattributed"] == pytest.approx(200e-9)


def test_mfu_and_flash_roofline_divide_the_benchmarks_count_by_the_peak():
    flops = types.SimpleNamespace(
        FLASH_KERNELS=FLASH.FLASH_KERNELS,
        train_flops_per_token=lambda sz, seq: 1000,
        attention_train_flops=lambda sz, batch, seq: 50 * batch)
    counters = {"step_program": "jit_train_step", "chips": 2,
                "tokens_per_step": 1024}
    obs = _obs(SYNTHETIC, counters, peaks={"bf16_flops": 1e12}, flops=flops)
    # 2 steps x 1024 tokens in 1900 ns, 1000 operations a token, 2 chips
    assert _reader("mfu")(obs) == pytest.approx(
        100 * 1000 * (2 * 1024 / 1900e-9) / (2 * 1e12))
    # 2 steps x 50 x (1024 / 512 sequences) / 2 chips, kernels ran 400 ns
    assert _reader("flash_roofline")(obs) == pytest.approx(
        100 * (2 * 50 * 2 / 2) / 1e12 / 400e-9)


GPT1 = {"layers": 12, "d_model": 768, "d_ff": 3072, "vocab": 40478,
        "causal": True}


def _serve_flops():
    cell = cells.load(pb_tiny.REPO, "gpt1.serve.closed-128")
    fam = cell.module("families", cell.config["family"])
    assert {k: fam.sizes(cell.config)[k] for k in GPT1} == GPT1
    assert cell.config["run"]["compute_dtype"] == "bfloat16"
    return cell, cell.module("flops", fam.FLOPS)


def test_the_kv_bytes_and_the_serving_operations_counted_by_hand():
    _, flops = _serve_flops()
    # one position: K and V, 12 layers x 768 wide x 2 bytes = 36 864 bytes;
    # the whole pool, 128 slots x 512 positions, is the cell's 2.4 GB
    assert flops.decode_kv_bytes(GPT1, 1, 2) == 36_864
    assert flops.decode_kv_bytes(GPT1, 128 * 512, 2) == 2_415_919_104
    assert flops.decode_kv_bytes(GPT1, 100, 4) == 2 * 36_864 * 100
    assert flops.ITEMSIZE["bfloat16"] == 2
    # a decoded token: 12 x (8 d^2 + 4 d d_ff) in the layers, 2 d vocab in
    # the head, and 4 d operations a layer for each position it attends over
    dense, head = 12 * (8 * 768 ** 2 + 4 * 768 * 3072), 2 * 768 * 40478
    assert (dense, head) == (169_869_312, 62_174_208)
    assert flops.serve_flops(GPT1, 1, 0, []) == dense + head
    assert flops.serve_flops(GPT1, 3, 500, []) == \
        3 * (dense + head) + 12 * 4 * 768 * 500
    # a prompt of 100: its tokens through the layers as a causal sequence
    # (2 s d a token and layer for the scores and values), one head
    assert flops.serve_flops(GPT1, 0, 0, [100]) == \
        100 * (dense + 12 * 2 * 100 * 768) + head
    assert flops.serve_flops(GPT1, 3, 500, [100, 100]) == \
        flops.serve_flops(GPT1, 3, 500, []) \
        + 2 * flops.serve_flops(GPT1, 0, 0, [100])


# a traced serve window of 2 000 ns: two decode steps whose kernel ran
# 3 x 100 ns inside the window (a fourth call began before it), over 50
# live positions in all
SERVE_TRACE = {
    "devices": {0: {
        "ops": [["paged_decode_attention.1", 900, 150],
                ["paged_decode_attention.1", 1100, 100],
                ["fusion.7", 1200, 300],
                ["paged_decode_attention.2", 1500, 100],
                ["paged_decode_attention.1", 2100, 100],
                ["reshape.4", 2200, 50]],
        "modules": [["jit_decode(5)", 1100, 600], ["jit_decode(5)", 2100, 200]]}},
    "host": [["pb.traced_window", 1000, 2000]]}


def _serve_obs(counters, peaks):
    cell, flops = _serve_flops()
    obs = _obs(SERVE_TRACE, dict(counters, chips=1), peaks=peaks, sizes=GPT1,
               flops=flops)
    obs.cell = cell
    return obs


def test_paged_decode_roofline_and_serve_mfu_on_a_hand_made_serve_trace():
    work = {"decode_tokens": 4, "live_positions": 50, "prompt_lens": [100]}
    peaks = {"bf16_flops": 1e15, "hbm_bytes_per_s": 1e13}
    obs = _serve_obs({"traced_work": work}, peaks)
    # 50 positions x 36 864 bytes over 1e13 bytes/s is 184.32 ns of the
    # kernel's 300 ns inside the window
    assert _reader("paged_decode_roofline")(obs) == pytest.approx(
        100 * 50 * 36_864 / 1e13 / 300e-9)
    dense, head = 169_869_312, 62_174_208
    need = 4 * (dense + head) + 12 * 4 * 768 * 50 \
        + 100 * (dense + 12 * 2 * 100 * 768) + head
    assert _reader("serve_mfu")(obs) == pytest.approx(
        100 * need / 2000e-9 / 1e15)
    # nothing to read: no traced counts, no kernel in the trace, no decoded
    # token, a device without the peak; never a 0
    for name in ("paged_decode_roofline", "serve_mfu"):
        assert _reader(name)(_serve_obs({}, peaks)) is None, name
        assert _reader(name)(_serve_obs({"traced_work": work}, {})) is None
    assert _reader("paged_decode_roofline")(_serve_obs(
        {"traced_work": dict(work, live_positions=0)}, peaks)) is None
    gone = _serve_obs({"traced_work": work}, peaks)
    gone.trace = {"devices": {0: {"ops": [["fusion.7", 1200, 300]],
                                  "modules": []}},
                  "host": SERVE_TRACE["host"]}
    assert _reader("paged_decode_roofline")(gone) is None
    assert _reader("serve_mfu")(gone) > 0      # the step's share stays


def test_the_two_serve_shares_at_the_cells_own_arithmetic():
    """PERF.md's figures for the cell (PR 30): 0.62 GB of live pages a step,
    1.66 ms of the kernel a step; 5 900 tokens a second.  The readers on a
    trace of that shape read the shares worked out by hand, inside (0, 100]."""
    positions = 96 * 175                   # 96 decoding slots at 175 live
    steps, kernel_ns = 180, 1_660_000
    ops = [["paged_decode_attention.3", 1000 + i * 16_000_000, kernel_ns]
           for i in range(steps)]
    doc = {"devices": {0: {"ops": ops, "modules": []}},
           "host": [["pb.traced_window", 0, steps * 16_000_000]]}
    work = {"decode_tokens": 96 * steps, "live_positions": positions * steps,
            "prompt_lens": [110] * steps}
    cell, flops = _serve_flops()
    obs = _obs(doc, {"traced_work": work, "chips": 1},
               peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
               sizes=GPT1, flops=flops)
    obs.cell = cell
    roofline = _reader("paged_decode_roofline")(obs)
    assert roofline == pytest.approx(
        100 * positions * 36_864 / 819e9 / 1.66e-3)
    assert 40 < roofline < 50
    mfu = _reader("serve_mfu")(obs)
    assert 0.5 < mfu < 2.0


def test_a_reader_that_finds_nothing_returns_nothing():
    obs = _obs({"devices": {0: {"ops": [], "modules": []}},
                "host": [["pb.traced_window", 0, 10]]}, {"chips": 1})
    for name in ("step_ms", "mfu", "flash_share", "flash_roofline",
                 "sim_error", "search_s", "decode_step_ms", "prefill_ms",
                 "slot_occupancy", "paged_decode_roofline", "serve_mfu"):
        assert _reader(name)(obs) is None, name


def test_the_recorded_chip_trace_reduces_to_its_known_answers():
    """Two steps of bert-base.train.4chip-searched on device 0, cut from a
    traced run of the harness on four v5e chips (PR 23)."""
    doc = xtrace.load(os.path.join(HERE, "data", "recorded_train_4chip.json"))
    with open(os.path.join(HERE, "data", "recorded_train_4chip.answers.json")) as f:
        want = json.load(f)
    counters = {"step_program": "jit_train_step", "chips": 4,
                "tokens_per_step": want["tokens_per_step"]}
    obs = _obs(doc, counters)
    for name in ("step_ms", "device_idle_share.train", "flash_share",
                 "collective_exposed_share"):
        assert _reader(name)(obs) == pytest.approx(want[name], rel=1e-9), name
    assert want["step_ms"] > 100 and 15 < want["flash_share"] < 35
    assert 0 < want["collective_exposed_share"] < 30
    names = {xtrace.op_kind(n) for n, _, _ in doc["devices"][0]["ops"]}
    assert any(n.startswith("flash_mha_bwd_dkv") for n in names)
    assert any(xtrace.COLLECTIVE.match(n) for n in names)


def test_the_flop_count_of_bert_base_to_the_digit():
    cell = cells.load(pb_tiny.REPO, "bert-base.train.1chip-b32-s512")
    fam = cell.module("families", cell.config["family"])
    flops = cell.module("flops", fam.FLOPS)
    sz = fam.sizes(cell.config)
    # per layer and token: 8 d^2 + 4 d d_ff + 4 s d, forward; x 12 layers x 3
    assert flops.forward_flops_per_token(sz, 512) == 188_743_680
    assert flops.train_flops_per_token(sz, 512) == 566_231_040
    # scores + values, two products forward and four backward
    assert flops.attention_train_flops(sz, 32, 512) == \
        6 * 2 * 512 * 512 * 768 * 32 * 12
    causal = dict(sz, causal=True)
    assert flops.forward_flops_per_token(causal, 512) == \
        12 * (8 * 768 ** 2 + 4 * 768 * 3072 + 2 * 512 * 768)
