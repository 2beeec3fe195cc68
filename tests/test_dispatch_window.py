"""Fused multi-step dispatch (FFConfig.steps_per_dispatch, ISSUE 4).

The parity suite pins BIT-IDENTICAL final params and per-step losses
for steps_per_dispatch ∈ {1, 4, 8} — K=1 is the historical
one-dispatch-per-step loop, K>1 runs the fused lax.scan window — on a
CPU mesh both single-device and distributed, and with gradient
accumulation enabled (the accumulation scan nests inside each window
step).  Plus: PrefetchLoader window staging, padded-tail training and
actual-sample throughput accounting.
"""

import json

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.data.dataloader import PrefetchLoader
from flexflow_tpu.parallel.mesh import MachineMesh

BS = 16
NFEAT = 12
NCLS = 5


def _model(k, accum=1, mesh_shape=None, pad=False, batch=BS):
    cfg = ff.FFConfig(batch_size=batch, compute_dtype="float32")
    cfg.steps_per_dispatch = k
    cfg.gradient_accumulation_steps = accum
    cfg.pad_tail_batches = pad
    m = ff.FFModel(cfg, mesh=MachineMesh(mesh_shape or {"n": 1}))
    x = m.create_tensor((batch, NFEAT), name="x")
    t = m.dense(x, 24, activation="relu")
    t = m.dense(t, NCLS)
    m.compile(ff.SGDOptimizer(lr=0.1, momentum=0.9), metrics=["accuracy"])
    m.init_layers(seed=0)
    return m


def _data(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, NFEAT)).astype(np.float32)
    y = rng.integers(0, NCLS, (n, 1)).astype(np.int32)
    return x, y


def _host_params(m):
    return {k: np.asarray(v) for k, v in m._params.items()}


# ----------------------------------------------------------------------
# parity: bit-identical final params AND per-step losses across K
# ----------------------------------------------------------------------
@pytest.mark.parametrize("mesh_shape", [{"n": 1}, {"n": 8}],
                         ids=["single", "distributed"])
@pytest.mark.parametrize("accum", [1, 2])
def test_window_parity_bitwise(mesh_shape, accum):
    x, y = _data(8 * BS)
    ref_losses = ref_params = None
    for k in (1, 4, 8):
        m = _model(k, accum=accum, mesh_shape=mesh_shape)
        m.fit(x, y, epochs=2, verbose=False)
        losses = m.last_epoch_losses.copy()
        params = _host_params(m)
        assert losses.shape == (8,)
        if k == 1:
            ref_losses, ref_params = losses, params
            continue
        np.testing.assert_array_equal(losses, ref_losses,
                                      err_msg=f"K={k} losses")
        for name in ref_params:
            np.testing.assert_array_equal(params[name], ref_params[name],
                                          err_msg=f"K={k} {name}")


def test_window_tail_shorter_than_k():
    """10 batches under K=4 dispatch as 4+4+2 — the short tail window
    runs the same scanned program at w=2, bit-identical to K=1."""
    x, y = _data(10 * BS)
    m1 = _model(1)
    m4 = _model(4)
    m1.fit(x, y, epochs=1, verbose=False)
    m4.fit(x, y, epochs=1, verbose=False)
    np.testing.assert_array_equal(m4.last_epoch_losses,
                                  m1.last_epoch_losses)
    for name, v in _host_params(m1).items():
        np.testing.assert_array_equal(_host_params(m4)[name], v,
                                      err_msg=name)
    assert m1._step == m4._step == 10


def test_train_window_verb_matches_train_batch():
    """The public train_window verb == K sequential train_batch calls."""
    x, y = _data(4 * BS)
    m1, mw = _model(1), _model(4)
    losses1 = [float(m1.train_batch(x[i * BS:(i + 1) * BS],
                                    y[i * BS:(i + 1) * BS]))
               for i in range(4)]
    window = tuple(a.reshape((4, BS) + a.shape[1:]) for a in (x, y))
    lossesw, sums = mw.train_window(window)
    np.testing.assert_array_equal(np.asarray(lossesw),
                                  np.asarray(losses1, np.float32))
    assert mw._step == 4
    assert np.asarray(sums["count"]).shape == (4,)
    for name, v in _host_params(m1).items():
        np.testing.assert_array_equal(_host_params(mw)[name], v,
                                      err_msg=name)


def test_steps_per_dispatch_validated_at_compile():
    cfg = ff.FFConfig(batch_size=BS, compute_dtype="float32")
    cfg.steps_per_dispatch = 0
    m = ff.FFModel(cfg, mesh=MachineMesh({"n": 1}))
    t = m.create_tensor((BS, NFEAT), name="x")
    m.dense(t, 2)
    with pytest.raises(ValueError, match="steps_per_dispatch"):
        m.compile(ff.SGDOptimizer(lr=0.1))


def test_warmup_compile_lowers_window_program():
    x, y = _data(BS)
    m = _model(4)
    m.warmup_compile(x, y)  # must not raise; lowers both step and window


# ----------------------------------------------------------------------
# PrefetchLoader window staging
# ----------------------------------------------------------------------
def test_loader_windows_match_batches():
    x, y = _data(7 * BS)
    m = _model(3)
    loader = PrefetchLoader(m, [x], y, batch_size=BS, steps_per_dispatch=3)
    seq = list(PrefetchLoader(m, [x], y, batch_size=BS))
    windows = list(loader.iter_windows())
    assert [w[0][0].shape[0] for w in windows] == [3, 3, 1]
    assert all(nv is None for _, nv in windows)
    flat = [tuple(np.asarray(a[i]) for a in w)
            for w, _ in windows for i in range(w[0].shape[0])]
    assert len(flat) == len(seq) == 7
    for got, want in zip(flat, seq):
        for g, wv in zip(got, want):
            np.testing.assert_array_equal(g, np.asarray(wv))


def test_loader_pad_tail_nvalid_and_counters():
    n = 2 * BS + 5
    x, y = _data(n)
    m = _model(2, pad=True)
    loader = PrefetchLoader(m, [x], y, batch_size=BS,
                            steps_per_dispatch=2, pad_tail=True)
    assert loader.num_steps == 3 and loader.tail_valid == 5
    assert loader.num_samples_used == n
    windows = list(loader.iter_windows())
    assert [w[0][0].shape[0] for w in windows] == [2, 1]
    np.testing.assert_array_equal(windows[0][1], [BS, BS])
    np.testing.assert_array_equal(windows[1][1], [5])
    # padded rows are zeros
    tail_x = np.asarray(windows[1][0][0][0])
    assert np.all(tail_x[5:] == 0)
    # without padding the tail is dropped and counters say so
    plain = PrefetchLoader(m, [x], y, batch_size=BS)
    assert plain.num_steps == 2 and plain.num_samples_used == 2 * BS


# ----------------------------------------------------------------------
# padded-tail training semantics
# ----------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 4])
def test_pad_tail_trains_tail_samples(k):
    """The masked padded step == a plain step on just the valid rows
    (mean over nvalid): pin against explicit ragged train_batch calls."""
    n = 2 * BS + 6
    x, y = _data(n)
    ref = _model(1)
    for lo, hi in ((0, BS), (BS, 2 * BS), (2 * BS, n)):
        ref.train_batch(x[lo:hi], y[lo:hi])  # ragged final batch
    m = _model(k, pad=True)
    m.fit(x, y, epochs=1, verbose=False)
    assert m._step == 3
    assert m.last_epoch_losses.shape == (3,)
    for name, v in _host_params(ref).items():
        np.testing.assert_allclose(_host_params(m)[name], v,
                                   rtol=1e-5, atol=1e-6, err_msg=name)
    # metric sums count only the VALID samples
    assert m.perf_metrics.train_all == n


def test_pad_tail_with_accum_parity():
    """Masked accumulation: per-microbatch masked sums carry the global
    denominator, so K and accumulation compose without drift."""
    n = BS + 8
    x, y = _data(n)
    runs = {}
    for k in (1, 2):
        m = _model(k, accum=2, pad=True)
        m.fit(x, y, epochs=1, verbose=False)
        runs[k] = (m.last_epoch_losses.copy(), _host_params(m))
    np.testing.assert_array_equal(runs[1][0], runs[2][0])
    for name, v in runs[1][1].items():
        np.testing.assert_array_equal(runs[2][1][name], v, err_msg=name)
    assert np.all(np.isfinite(runs[1][0]))


def test_throughput_counts_actual_samples(capsys):
    """The THROUGHPUT line's sample count reflects what was trained:
    padded-tail runs count the tail, plain runs do not."""
    n = BS + 4
    x, y = _data(n)
    m = _model(1, pad=True)
    m.fit(x, y, epochs=1, verbose=True)
    out = capsys.readouterr().out
    assert f'"samples": {n}' in out  # epoch JSON event
    m2 = _model(1)
    m2.fit(x, y, epochs=1, verbose=True)
    out2 = capsys.readouterr().out
    assert f'"samples": {BS}' in out2


def test_epoch_event_records_dispatches(capsys):
    x, y = _data(8 * BS)
    m = _model(4)
    m.fit(x, y, epochs=1, verbose=False)
    events = [json.loads(line) for line in capsys.readouterr().out.splitlines()
              if line.startswith("{")]
    ev = [e for e in events if e.get("event") == "epoch"][-1]
    assert ev["steps_per_dispatch"] == 4
    assert ev["dispatches"] == 2
    assert ev["dispatch_ms"] > 0


# ----------------------------------------------------------------------
# evaluate / predict: device-side accumulation satellites
# ----------------------------------------------------------------------
def test_evaluate_unchanged_numerics():
    x, y = _data(3 * BS + 7)
    m = _model(1)
    loss, pm = m.evaluate(x, y)
    assert np.isfinite(loss)
    assert pm.train_all == 3 * BS + 7  # masked tail counted once
    # per-example mean cross-check on the untrained-but-deterministic net
    preds = m.predict(x)
    assert preds.shape == (3 * BS + 7, NCLS)
    logp = preds - np.log(np.sum(np.exp(preds), axis=-1, keepdims=True))
    want = -np.mean(logp[np.arange(len(x)), y[:, 0]])
    np.testing.assert_allclose(loss, want, rtol=1e-4)


def test_predict_matches_batched_forward():
    x, y = _data(2 * BS + 3)
    m = _model(1)
    full = m.predict(x, batch_size=BS)
    assert full.shape == (2 * BS + 3, NCLS)
    again = m.predict(x, batch_size=2 * BS + 3)
    np.testing.assert_allclose(full, again, rtol=1e-5, atol=1e-6)
