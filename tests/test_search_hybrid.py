"""ISSUE 20: hybrid exact/stochastic strategy search.

Pins (a) the mcmc mode's fixed-seed walk bit-identical to the
pre-hybrid HEAD, (b) the package DP against exhaustive enumeration,
(c) the decomposition pass's chain/diamond recognition, (d) the
singleton/fully-decomposable early exits that stop the anneal burning
budget on no-op proposals, and (e) the warm-start BestStrategyStore
round trip.  All analytic-mode — CPU-only, tier-1 safe.
"""

import json

import pytest

from flexflow_tpu.config import FFConfig, ParallelConfig
from flexflow_tpu.model import FFModel
from flexflow_tpu.search.decompose import (MAX_EXACT_CANDIDATES, decompose,
                                           data_parallel_strategies,
                                           fully_decomposable, graph_digest,
                                           solve_chain,
                                           solve_chain_exhaustive,
                                           solve_regions)
from flexflow_tpu.search.hybrid import BestStrategyStore, validate_store
from flexflow_tpu.search.mcmc import legal_configs, search
from flexflow_tpu.search.simulator import Simulator
from flexflow_tpu.strategy.proto import strategy_digest

# captured at the pre-hybrid HEAD (PR 19): search(mlp, 8, budget=80,
# seed=0) — the mcmc mode must keep reproducing this walk bit-for-bit
GOLDEN_DIGEST = "d584a363574e0539"
GOLDEN_MESH = {"c": 8}
GOLDEN_MS = 0.01351351


def _mlp_model():
    cfg = FFConfig(batch_size=4096, compute_dtype="float32")
    cfg.mesh_shape = {"n": 1}
    m = FFModel(cfg)
    t = m.create_tensor((4096, 256))
    t = m.dense(t, 256, activation="relu")
    t = m.dense(t, 256, activation="relu")
    t = m.dense(t, 16)
    return m


def _branchy_model():
    """Two source denses feeding a concat chain: the branches can't be
    frozen (no common fork op), so hybrid has residual work."""
    cfg = FFConfig(batch_size=64, compute_dtype="float32")
    cfg.mesh_shape = {"n": 1}
    m = FFModel(cfg)
    x = m.create_tensor((64, 128))
    a = m.dense(x, 128, activation="relu")
    b = m.dense(x, 128, activation="relu")
    c = m.concat([a, b], axis=1)
    m.dense(c, 32)
    return m


def _diamond_model():
    """A true reconvergent diamond: fork op -> 2 branches -> join."""
    cfg = FFConfig(batch_size=64, compute_dtype="float32")
    cfg.mesh_shape = {"n": 1}
    m = FFModel(cfg)
    x = m.create_tensor((64, 64))
    f = m.dense(x, 64, activation="relu")
    a = m.dense(f, 64, activation="relu")
    b = m.dense(f, 64, activation="relu")
    j = m.concat([a, b], axis=1)
    m.dense(j, 16)
    return m


# ---------------------------------------------------------------------------
# mcmc mode stays bit-identical (the PR's no-regression acceptance pin)
# ---------------------------------------------------------------------------

def test_mcmc_mode_fixed_seed_bit_identical_to_head():
    m = _mlp_model()
    for chains in (1, 4):
        best, mesh, t = search(m.layers, 8, budget=80, seed=0,
                               chains=chains)
        assert strategy_digest(best) == GOLDEN_DIGEST
        assert {a: s for a, s in mesh.items() if s > 1} == GOLDEN_MESH
        assert t * 1e3 == pytest.approx(GOLDEN_MS, rel=1e-5)


def test_search_rejects_unknown_mode():
    m = _mlp_model()
    with pytest.raises(ValueError, match="unknown search mode"):
        search(m.layers, 8, budget=4, seed=0, mode="exhaustive")


# ---------------------------------------------------------------------------
# decomposition pass
# ---------------------------------------------------------------------------

def test_decompose_pure_chain():
    m = _mlp_model()
    regions, residual = decompose(m.layers)
    assert [r.kind for r in regions] == ["chain"]
    assert sorted(regions[0].ops) == list(range(len(m.layers)))
    assert residual == []
    assert fully_decomposable(m.layers)


def test_decompose_branchy_residual():
    m = _branchy_model()
    regions, residual = decompose(m.layers)
    names = [op.name for op in m.layers]
    resid_names = {names[i] for i in residual}
    # the two source denses have no common fork op -> residual; the
    # concat->dense tail is a chain region
    assert resid_names == {"dense", "dense_1"}
    assert any(r.kind == "chain" for r in regions)
    assert not fully_decomposable(m.layers)


def test_decompose_reconvergent_diamond():
    m = _diamond_model()
    regions, residual = decompose(m.layers)
    kinds = {r.kind for r in regions}
    assert "diamond" in kinds
    dia = next(r for r in regions if r.kind == "diamond")
    names = [op.name for op in m.layers]
    assert names[dia.fork] == "dense"       # the fork dense
    assert names[dia.join] == "concat"      # reconvergence point
    # every op lands in exactly one region or the residual
    covered = sorted(i for r in regions for i in r.ops) + sorted(residual)
    assert sorted(covered) == list(range(len(m.layers)))


def test_graph_digest_stable_across_builds():
    assert graph_digest(_mlp_model().layers) == \
        graph_digest(_mlp_model().layers)
    assert graph_digest(_mlp_model().layers) != \
        graph_digest(_diamond_model().layers)


# ---------------------------------------------------------------------------
# exact DP vs exhaustive enumeration (the ISSUE's pinned equivalence)
# ---------------------------------------------------------------------------

def test_chain_dp_matches_exhaustive():
    m = _mlp_model()
    sim = Simulator(num_devices=8)
    mesh = {a: 1 for a in ("n", "c", "h", "w", "s", "e", "p")}
    mesh["c"] = 8
    cands = {op.name: legal_configs(op, mesh, seed=0) for op in m.layers}
    got_cfg, got_cost = solve_chain(sim, m.layers, cands)
    exp_cfg, exp_cost = solve_chain_exhaustive(sim, m.layers, cands)
    assert got_cost == pytest.approx(exp_cost, rel=1e-9)
    assert {n: pc.dims for n, pc in got_cfg.items()} == \
        {n: pc.dims for n, pc in exp_cfg.items()}


def test_solve_regions_covers_diamond_exactly():
    m = _diamond_model()
    sim = Simulator(num_devices=4)
    mesh = {a: 1 for a in ("n", "c", "h", "w", "s", "e", "p")}
    mesh["c"] = 4
    regions, _ = decompose(m.layers)
    cands = {op.name: legal_configs(op, mesh, seed=0) for op in m.layers}
    frozen, frozen_idx, total = solve_regions(
        sim, m.layers, regions, cands,
        max_exact_candidates=MAX_EXACT_CANDIDATES)
    covered = {m.layers[i].name for i in frozen_idx}
    assert set(frozen) == covered
    assert total < float("inf")


def test_diamond_dp_matches_exhaustive():
    """solve_diamond against brute-force enumeration of the SAME
    additive objective (node costs + pairwise edge transitions over the
    region's ops — non-edges contribute zero)."""
    import itertools

    from flexflow_tpu.search.decompose import (node_cost, solve_diamond,
                                               transition_cost)
    m = _diamond_model()
    sim = Simulator(num_devices=4)
    mesh = {a: 1 for a in ("n", "c", "h", "w", "s", "e", "p")}
    mesh["c"] = 4
    regions, _ = decompose(m.layers)
    dia = next(r for r in regions if r.kind == "diamond")
    cands = {op.name: legal_configs(op, mesh, seed=0) for op in m.layers}
    got_cfg, got_cost = solve_diamond(sim, m.layers, dia, cands)

    idx = sorted(dia.ops)
    names = [m.layers[i].name for i in idx]

    def cost(cfg):
        tot = sum(node_cost(sim, m.layers[i], cfg[m.layers[i].name])
                  for i in idx)
        for i in idx:
            for j in idx:
                if i != j:
                    tot += transition_cost(sim, m.layers[i],
                                           cfg[m.layers[i].name],
                                           m.layers[j],
                                           cfg[m.layers[j].name])
        return tot

    best_t = min(cost(dict(zip(names, combo)))
                 for combo in itertools.product(
                     *(cands[n] for n in names)))
    assert got_cost == pytest.approx(best_t, rel=1e-9)
    assert cost(got_cfg) == pytest.approx(best_t, rel=1e-9)


# ---------------------------------------------------------------------------
# early exits (the ISSUE 20 budget-burn bugfix)
# ---------------------------------------------------------------------------

def test_mcmc_singleton_early_exit():
    """One device, one mesh, singleton legal_configs everywhere: a huge
    budget must return instantly with zero proposals — and the same
    result a zero-budget search reports."""
    import time
    m = _mlp_model()
    stats = {}
    t0 = time.perf_counter()
    best, mesh, t = search(m.layers, 1, budget=200_000, seed=0,
                           stats=stats)
    assert time.perf_counter() - t0 < 5.0
    assert stats["proposals"] == 0
    assert stats["proposals_saved"] == 200_000
    b0, m0, t0_ = search(m.layers, 1, budget=0, seed=0)
    assert strategy_digest(best) == strategy_digest(b0)
    assert t == t0_


def test_hybrid_fully_decomposable_zero_proposals():
    m = _mlp_model()
    stats = {}
    best, mesh, t = search(m.layers, 8, budget=80, seed=0, mode="hybrid",
                           stats=stats)
    assert stats["mode"] == "hybrid"
    assert stats["fully_decomposable"] is True
    assert stats["proposals"] == 0
    assert stats["proposals_saved"] == 80
    assert stats["regions"] == 1 and stats["residual_ops"] == 0
    # the exact DP lands on the same optimum the anneal converges to
    assert strategy_digest(best) == GOLDEN_DIGEST
    assert t * 1e3 == pytest.approx(GOLDEN_MS, rel=1e-5)


def test_hybrid_seeded_determinism_across_chain_counts():
    """Same seed + mode=hybrid -> identical digest for chains=1 and
    chains=4 (the satellite pin, on the fully-decomposable graph where
    the exact path decides the answer before any chain forks)."""
    m = _mlp_model()
    digests = set()
    for chains in (1, 4):
        best, _, _ = search(m.layers, 8, budget=80, seed=0,
                            mode="hybrid", chains=chains)
        digests.add(strategy_digest(best))
    assert len(digests) == 1


def test_hybrid_run_to_run_deterministic_with_residual():
    m = _branchy_model()
    stats = {}
    runs = [search(m.layers, 8, budget=40, seed=3, mode="hybrid",
                   stats=stats)
            for _ in range(2)]
    assert strategy_digest(runs[0][0]) == strategy_digest(runs[1][0])
    assert runs[0][2] == runs[1][2]
    # what a residual run counts: every op is exact or annealed, the
    # anneal's proposals and how long the best took to appear
    assert stats["exact_ops"] + stats["residual_ops"] == len(m.layers)
    assert 0 <= stats["accepted"] <= stats["proposals"] <= 40
    assert stats["time_to_best_ms"] >= 0


# ---------------------------------------------------------------------------
# hybrid results verify clean + never lose to mcmc at the same budget
# ---------------------------------------------------------------------------

def test_hybrid_strategies_lint_clean():
    """ffcheck cross-check (satellite): the hybrid winner must verify
    with zero ERROR/WARN diagnostics on its own mesh."""
    from flexflow_tpu.analysis import Severity, verify
    for model in (_mlp_model(), _diamond_model()):
        best, mesh, t = search(model.layers, 8, budget=40, seed=0,
                               mode="hybrid")
        report = verify(model.layers, best, mesh_shape=mesh,
                        num_devices=8, check_resharding=False)
        bad = [d for d in report
               if d.severity in (Severity.WARN, Severity.ERROR)]
        assert not bad, [f"{d.code}: {d.message}" for d in bad]


def test_hybrid_not_worse_than_mcmc_same_budget():
    for model in (_mlp_model(), _branchy_model(), _diamond_model()):
        _, _, t_mcmc = search(model.layers, 8, budget=60, seed=0)
        _, _, t_hyb = search(model.layers, 8, budget=60, seed=0,
                             mode="hybrid")
        assert t_hyb <= t_mcmc * (1 + 1e-9)


# ---------------------------------------------------------------------------
# warm-start BestStrategyStore
# ---------------------------------------------------------------------------

def test_best_strategy_store_roundtrip(tmp_path):
    path = str(tmp_path / "best_known.json")
    m = _branchy_model()
    stats = {}
    best, mesh, t = search(m.layers, 8, budget=40, seed=0, mode="hybrid",
                           warm_start=path, stats=stats)
    # the run recorded its winner
    store = BestStrategyStore.load(path)
    key = BestStrategyStore.key(graph_digest(m.layers), 8, None)
    hit = store.get(key)
    assert hit is not None
    prior, prior_mesh, prior_t = hit
    assert strategy_digest(prior) == strategy_digest(best)
    # the table stores a rounded ms figure (JSON stability)
    assert prior_t == pytest.approx(t, rel=1e-4)
    with open(path) as f:
        assert validate_store(json.load(f)) == []
    # second run finds the stored entry and reports the transfer
    stats2 = {}
    best2, _, t2 = search(m.layers, 8, budget=40, seed=0, mode="hybrid",
                          warm_start=path, stats=stats2)
    assert stats2["warm_start_used"] is True
    assert t2 <= t * (1 + 1e-9)


def test_best_strategy_store_keeps_better_entry(tmp_path):
    path = str(tmp_path / "best_known.json")
    m = _mlp_model()
    dp = data_parallel_strategies(m.layers, 8)
    store = BestStrategyStore()
    key = BestStrategyStore.key(graph_digest(m.layers), 8, None)
    assert store.put(key, dp, {"n": 8}, 1.0)
    assert not store.put(key, dp, {"n": 8}, 2.0)  # worse: rejected
    assert store.put(key, dp, {"n": 8}, 0.5)
    store.save(path)
    assert BestStrategyStore.load(path).get(key)[2] == 0.5


def test_validate_store_flags_corruption(tmp_path):
    m = _mlp_model()
    store = BestStrategyStore()
    key = BestStrategyStore.key(graph_digest(m.layers), 8, None)
    store.put(key, data_parallel_strategies(m.layers, 8), {"n": 8}, 1.0)
    data = store.to_json()
    assert validate_store(data) == []
    bad = json.loads(json.dumps(data))
    bad["kind"] = "something_else"
    bad["entries"]["only-one-part"] = list(bad["entries"].values())[0]
    assert validate_store(bad)


def test_config_parses_search_mode_flags():
    cfg = FFConfig.parse_args(["--search-mode", "hybrid",
                               "--best-known", "/tmp/bk.json",
                               "--budget", "10"])
    assert cfg.search_mode == "hybrid"
    assert cfg.best_known_file == "/tmp/bk.json"
    with pytest.raises(ValueError):
        FFConfig.parse_args(["--search-mode", "genetic"])


def test_shared_dp_baseline_shape():
    """The dedup satellite's shared helper caps the data axis at the
    batch dimension, exactly like the script/test copies it replaced."""
    m = _mlp_model()
    dp = data_parallel_strategies(m.layers, 8)
    for op in m.layers:
        assert dp[op.name].dims[0] == min(8, op.outputs[0].shape[0])
        assert all(d == 1 for d in dp[op.name].dims[1:])
