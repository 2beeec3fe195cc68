"""Round-4 search-fidelity fixes (VERDICT r3 #4, #7, #8):

* liveness-aware peak-memory: view/fused op outputs are not resident,
  remat halves retained activations — an over-estimating legality check
  silently bans good strategies (the inverse of the round-2 bug);
* slice-aware weight sync: replica groups crossing a slice pay the DCN
  term (reference simulator.cu:27-29 inter-node fabric, previously dead
  code in the search objective);
* measure mode times TP sub-problems via Op.sub_problem (full weights +
  channel-projected inputs used to shape-error every TP config to inf).
"""

import math

import numpy as np

from flexflow_tpu.config import ParallelConfig
from flexflow_tpu.ops.conv import Conv2D
from flexflow_tpu.ops.elementwise import ElementUnary
from flexflow_tpu.ops.linear import Embedding, Linear
from flexflow_tpu.search.cost_model import (DeviceSpec, allreduce_time,
                                            op_memory_bytes)
from flexflow_tpu.search.simulator import Simulator
from flexflow_tpu.tensor import Tensor


# ------------------------------------------------------------------
# peak memory (VERDICT r3 #7)

def _relu_chain(n_layers=50, batch=256, width=2048):
    """Dense->relu chain where every relu output used to double-count."""
    t = Tensor((batch, width), name="x")
    layers = []
    for i in range(n_layers):
        fc = Linear(f"fc{i}", t, width)
        t = fc.outputs[0]
        act = ElementUnary(f"relu{i}", t, "relu")
        t = act.outputs[0]
        layers += [fc, act]
    return layers


def test_fused_op_outputs_not_resident():
    t = Tensor((256, 2048), name="x")
    act = ElementUnary("relu", t, "relu")
    assert op_memory_bytes(act, (1, 1)) == 0.0
    fc = Linear("fc", t, 2048)
    assert op_memory_bytes(fc, (1, 1)) > 0.0


def test_deep_chain_not_banned_at_realistic_hbm():
    """A 50-layer chain's TRUE residency (linear outputs, not relu copies)
    must fit where the old double-count said OOM; a genuinely-OOM
    strategy must still score inf."""
    layers = _relu_chain()
    strategies = {op.name: ParallelConfig.data_parallel(1, 2)
                  for op in layers}
    sim = Simulator(num_devices=1, use_native=False)
    peak = sim.peak_memory_bytes(layers, strategies)
    # params: 50 * 2048^2 * 12B = 2.5GB; linear acts: 50 * 1MB = 50MB
    act_bytes = 50 * 256 * 2048 * 2
    # capacity between true residency and the old relu-inflated estimate
    # (legality charges peak * XLA_TEMP_FACTOR, the measured compiler
    # overhead — BASELINE.md round-5 memory_analysis validation)
    from flexflow_tpu.search.cost_model import XLA_TEMP_FACTOR
    cap = (peak + act_bytes / 2) * XLA_TEMP_FACTOR
    tight = DeviceSpec(hbm_capacity=cap)
    assert np.isfinite(Simulator(spec=tight, num_devices=1,
                                 use_native=False
                                 ).simulate(layers, strategies))
    # genuinely OOM (params alone exceed capacity) still banned
    tiny = DeviceSpec(hbm_capacity=1e9)
    assert math.isinf(Simulator(spec=tiny, num_devices=1, use_native=False
                                ).simulate(layers, strategies))


def test_remat_scales_retained_activations():
    """Under sqrt(N)-segmented remat (model.py _execute_remat) the
    resident activation fraction is 2/sqrt(N): segment boundaries plus
    one recomputed segment interior (validated against jax
    saved_residuals in test_remat_memory.py)."""
    n = 10
    layers = _relu_chain(n_layers=n)
    strategies = {op.name: ParallelConfig.data_parallel(1, 2)
                  for op in layers}
    base = Simulator(num_devices=1, use_native=False)
    remat = Simulator(num_devices=1, use_native=False, remat=True)
    p0 = base.peak_memory_bytes(layers, strategies)
    p1 = remat.peak_memory_bytes(layers, strategies)
    # 10 fc outputs materialize (relu outputs are _UNMATERIALIZED);
    # the segmentation factor runs over the full layer list (fc + relu,
    # matching _execute_remat's split of self.layers)
    act = n * 256 * 2048 * 2
    expected_drop = act * (1.0 - 2.0 / math.sqrt(len(layers)))
    assert abs((p0 - p1) - expected_drop) < 1e-6 * p0


# ------------------------------------------------------------------
# slice-aware weight sync (VERDICT r3 #4)

def test_allreduce_crossing_slices_pays_dcn():
    spec = DeviceSpec()
    b = 64 << 20
    within = allreduce_time(b, 8, spec)  # one ICI domain
    crossing = allreduce_time(b, 8, spec, members_per_slice=4)
    assert crossing > within
    # the DCN term scales with the slow fabric: halving dcn_bw ~doubles it
    slow = DeviceSpec(dcn_bw=spec.dcn_bw / 2)
    assert allreduce_time(b, 8, slow, members_per_slice=4) > crossing


def test_two_slice_mesh_prefers_tp_within_dp_across():
    """On a 2-slice 8-chip machine a weight-heavy model should cost LESS
    with TP inside the slice (DCN moves 1/c of the bytes) than pure DP
    (DCN moves the full weight), and the slice boundary must penalize DP
    RELATIVELY more than TP (that's what steers the search toward
    TP-within / DP-across on multi-slice meshes)."""
    t = Tensor((512, 4096), name="x")
    fc = Linear("fc", t, 4096)
    dp8 = {"fc": ParallelConfig.data_parallel(8, 2)}
    tp4dp2 = {"fc": ParallelConfig(dims=(2, 4),
                                   device_ids=tuple(range(8)))}
    two_slice = Simulator(num_devices=8, devices_per_slice=4,
                          use_native=False)
    one_slice = Simulator(num_devices=8, use_native=False)
    assert (two_slice.simulate([fc], dp8)
            > two_slice.simulate([fc], tp4dp2))
    # the slice boundary itself must be visible in the objective: any
    # strategy whose weight sync crosses it costs more than on one slice
    assert (two_slice.simulate([fc], dp8)
            > one_slice.simulate([fc], dp8))
    assert (two_slice.simulate([fc], tp4dp2)
            > one_slice.simulate([fc], tp4dp2))


def test_search_plumbs_devices_per_slice():
    from flexflow_tpu.search.mcmc import search
    t = Tensor((64, 256), name="x")
    fc = Linear("fc", t, 256)
    _, _, t1 = search([fc], 8, budget=20, seed=0, devices_per_slice=4)
    assert np.isfinite(t1)


# ------------------------------------------------------------------
# measure mode via the calibrated profiler (VERDICT r3 #8)

def test_sub_problem_shapes():
    t = Tensor((64, 128), name="x")
    fc = Linear("fc", t, 256)
    ins, ws = fc.sub_problem((2, 4))
    assert ins == [(32, 128)]  # input replicated at full width
    assert ws[fc.w_kernel.name] == (64, 128)  # out rows sharded by 4
    assert ws[fc.w_bias.name] == (64,)

    ids = Tensor((64, 16), dtype="int32", name="ids")
    emb = Embedding("emb", ids, 1000, 64, aggr="sum")
    ins, ws = emb.sub_problem((2, 2))
    assert ins == [(32, 16)]  # bag dim never splits
    assert ws[emb.w_table.name] == (1000, 32)

    img = Tensor((8, 16, 32, 32), name="img")
    conv = Conv2D("cv", img, 64, 3, 3, 1, 1, 1, 1)
    ins, ws = conv.sub_problem((2, 4, 2, 1))
    assert ins == [(4, 16, 16, 32)]  # input channels stay full
    assert ws[conv.w_kernel.name] == (16, 16, 3, 3)


def test_residual_add_output_stays_resident():
    # a residual trunk (ElementBinary add) IS a retained HBM buffer —
    # only unary epilogues/views are fused away
    from flexflow_tpu.ops.elementwise import ElementBinary
    a = Tensor((256, 2048), name="a")
    b = Tensor((256, 2048), name="b")
    add = ElementBinary("res", a, b, "add")
    assert op_memory_bytes(add, (1, 1)) == 256 * 2048 * 2


def test_measure_mode_lstm_tp_finite():
    # LSTM's gate split is tied to hidden_size: c-split configs time at
    # full width (upper bound) instead of shape-erroring to inf
    from flexflow_tpu.ops.rnn import LSTM
    x = Tensor((8, 4, 32), name="x")
    lstm = LSTM("lstm", x, 32)
    sim = Simulator(num_devices=4, measure=True, use_native=False)
    assert 0 < sim._op_time(lstm, (2, 1, 2), backward=False) < np.inf


def test_sub_problem_indivisible_input_replicates():
    # kv seq 50 with an s-degree that divides the 128-long query only:
    # the graph simulator replicates such inputs; measure mode must too
    from flexflow_tpu.ops.attention import MultiHeadAttention
    q = Tensor((4, 128, 64), name="q")
    kv = Tensor((4, 50, 64), name="kv")
    attn = MultiHeadAttention("xattn", q, kv, kv, 64, 4)
    ins, _ = attn.sub_problem((1, 4, 1))
    assert ins[0] == (4, 32, 64)  # query splits
    assert ins[1] == (4, 50, 64)  # kv replicated, not banned


def test_measure_mode_times_tp_subproblem():
    """A c-split Linear must measure FINITE (full-weight + projected-input
    used to shape-error to inf, so measure-mode search could never pick
    TP) and cheaper-or-equal vs the unsplit op."""
    t = Tensor((32, 256), name="x")
    fc = Linear("fc", t, 512)
    sim = Simulator(num_devices=4, measure=True, use_native=False)
    t_full = sim._op_time(fc, (1, 1), backward=False)
    t_tp = sim._op_time(fc, (1, 4), backward=False)
    assert 0 < t_full < np.inf
    assert 0 < t_tp < np.inf
    b_full = sim._op_time(fc, (1, 1), backward=True)
    assert 0 < b_full < np.inf


def test_calibrated_backward_overheads(monkeypatch):
    """The r5 on-chip calibration's two systematic under-predictions are
    corrected in analytic mode (Op.backward_overhead): max-pool bwd 1.9x
    (SelectAndScatter), stride>1 conv dgrad 3.4x (dilated lowering).
    Avg pool and stride-1 convs stay on the 2x-forward model."""
    from flexflow_tpu.ops.conv import Pool2D
    from flexflow_tpu.search.cost_model import DEFAULT_SPEC, op_compute_time

    t = Tensor((8, 64, 28, 28), name="x")
    mx = Pool2D("mp", t, 2, 2, 2, 2, 0, 0, pool_type="max")
    av = Pool2D("ap", t, 2, 2, 2, 2, 0, 0, pool_type="avg")
    assert mx.backward_overhead() == 1.9 and av.backward_overhead() == 1.0
    b_mx = op_compute_time(mx, (1,), DEFAULT_SPEC, backward=True)
    b_av = op_compute_time(av, (1,), DEFAULT_SPEC, backward=True)
    launch = DEFAULT_SPEC.kernel_launch
    np.testing.assert_allclose(b_mx - launch, 1.9 * (b_av - launch),
                               rtol=1e-6)

    c1 = Conv2D("c1", t, 64, 3, 3, 1, 1, 1, 1)
    c2 = Conv2D("c2", t, 64, 3, 3, 2, 2, 1, 1)
    assert c1.backward_overhead() == 1.0 and c2.backward_overhead() == 3.4
    f2 = op_compute_time(c2, (1,), DEFAULT_SPEC, backward=False)
    b2 = op_compute_time(c2, (1,), DEFAULT_SPEC, backward=True)
    assert b2 > 2.0 * (f2 - launch)  # strictly above the naive 2x model


def test_sparse_table_sync_costs_rows_not_table():
    """An embedding table on the sparse-update path syncs only the
    touched row gradients across replicas — the dense costing (full
    table allreduce) overestimates DLRM/NMT-class sync by orders of
    magnitude."""
    ids = Tensor((64, 1), "int32", name="ids")
    emb = Embedding("emb", ids, 100000, 64)
    pc = {"emb": ParallelConfig.data_parallel(4, 2)}
    dense_sim = Simulator(num_devices=4, use_native=False)
    sparse_sim = Simulator(num_devices=4, use_native=False,
                           sparse_tables={emb.w_table.name})
    sync_dense = dense_sim._op_plan(emb, pc)[4]
    sync_sparse = sparse_sim._op_plan(emb, pc)[4]
    assert sync_sparse > 0
    # table 100k x 64 f32 = 25.6 MB vs rows 64 x 64 x 4 = 16 KB
    assert sync_dense / sync_sparse > 50, (sync_dense, sync_sparse)


def test_sparse_table_memory_excludes_dense_grad():
    """HBM legality: a sparse-update table resides as params ONLY — the
    dense path's table-shaped gradient (+ slots) never materializes, so
    big-table strategies must not be falsely inf'd."""
    from flexflow_tpu.search.cost_model import op_memory_bytes

    ids = Tensor((64, 1), "int32", name="ids")
    emb = Embedding("emb", ids, 1000000, 64)
    table = emb.w_table.name
    dense = op_memory_bytes(emb, (4, 1), opt_slot_bytes=0)
    sparse = op_memory_bytes(emb, (4, 1), opt_slot_bytes=0,
                             sparse_tables={table})
    # dense charges params+grads (8 B/param); sparse params only (4)
    assert dense > 1.9 * sparse, (dense, sparse)

    s_dense = Simulator(num_devices=4, use_native=False)
    s_sparse = Simulator(num_devices=4, use_native=False,
                         sparse_tables={table})
    pc = {"emb": ParallelConfig.data_parallel(4, 2)}
    m_dense = s_dense.peak_memory_bytes([emb], pc)
    m_sparse = s_sparse.peak_memory_bytes([emb], pc)
    assert m_dense > m_sparse > 0
