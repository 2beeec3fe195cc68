"""Fixtures the serving tests share: the smallest post-norm language model
the generation engine serves, a prefill/decode pair behind a router, and
the cross-engine reconciliation check.  Not a test file (like
``tests/subproc.py``), so ``--dist loadfile``'s order of files does not
move with it.
"""

from typing import Dict, List

VOCAB = 128


def _build_lm(slots: int, max_seq: int, d_model: int, num_heads: int,
              num_layers: int, seed: int):
    import flexflow_tpu as ff
    from flexflow_tpu.models import build_transformer_lm
    from flexflow_tpu.parallel.mesh import MachineMesh

    cfg = ff.FFConfig(batch_size=4, compute_dtype="float32", seed=seed)
    cfg.serve_gen_slots = slots
    cfg.serve_gen_max_seq = max_seq
    m = build_transformer_lm(
        cfg, num_layers=num_layers, d_model=d_model, num_heads=num_heads,
        d_ff=4 * d_model, seq_len=max_seq, vocab_size=VOCAB)[0]
    m.compile(ff.SGDOptimizer(lr=0.01), mesh=MachineMesh({"n": 1}))
    m.init_layers(seed=seed)
    return m


def _reconciled(snaps: List[Dict]) -> bool:
    """submitted == sum of terminals, SUMMED across the engines — a
    migrated stream submits on one engine and terminates on another,
    so only the cross-engine sum balances."""
    submitted = sum(s["submitted"] for s in snaps)
    terminal = sum(s["requests"] + s["rejected"] + s["shed"]
                   + s["expired"] + s["errors"] + s["cancelled"]
                   for s in snaps)
    return submitted == terminal


def build_disagg(model, slots: int, max_seq: int, chunk: int,
                 prefix_cache: str = "off", pf_pace_s: float = 0.002):
    """One prefill-role + one decode-role fleet over shared weights,
    fronted by a router.  The decode engine is PINNED to a second jax
    device when one exists (``--xla_force_host_platform_device_count``
    gives single-host CPU runs one) — without its own device the
    decode host's steps would queue behind prefill programs on the
    shared executor, which is exactly the interference disaggregation
    removes.  Returns (router, fleets, engines); the caller stops the
    router first, then the fleets."""
    import jax

    from flexflow_tpu.serving.cluster import FleetRouter
    from flexflow_tpu.serving.fleet import FleetEngine
    from flexflow_tpu.serving.generation import GenerationEngine

    devs = jax.devices()
    dc_dev = devs[1] if len(devs) > 1 else None
    pf_eng = GenerationEngine(model, slots=slots, max_seq=max_seq,
                              stats_every=0, prefill_chunk=chunk,
                              prefix_cache=prefix_cache)
    dc_eng = GenerationEngine(model, slots=slots, max_seq=max_seq,
                              stats_every=0, prefix_cache=prefix_cache,
                              device=dc_dev)
    # prefill-host pacing (FleetEngine.pace_s): on a shared substrate
    # the prefill role hands the core to the decode host at every op
    # boundary — TTFT cost ~pace_s per chunk, decode-tail win ~a whole
    # scheduler quantum per collision
    pf = FleetEngine(pace_s=pf_pace_s)
    dc = FleetEngine()
    pf.add_engine("lm", pf_eng)
    dc.add_engine("lm", dc_eng)
    pf.start()
    dc.start()
    router = FleetRouter()
    router.add_host("pf0", pf, role="prefill")
    router.add_host("dc0", dc, role="decode")
    router.start()
    return router, (pf, dc), (pf_eng, dc_eng)
