"""Traffic kind ``serve_closed``: N clients in a closed loop on one
``GenerationEngine``; each submits its next request when its last stream has
ended (callers that wait for a reply).

The requests' sizes are a fixed grid of the mix's distributions in a fixed
order (the same for every seed; the seed draws the token ids and the
weights), so two runs do the same work.  After the engine's own warm-up the clients run ``warm_s``
seconds untimed (set-up), then the window opens for exactly ``--seconds``:

* ``serve_tokens_per_s``: tokens that reached a client inside the window,
  over its seconds;
* ``itl_p95_ms``: over every gap between consecutive tokens of one stream
  whose later token arrived inside the window;
* ``ttft_p50_ms``/``ttft_p95_ms``: over the requests submitted inside the
  window, client-side time from ``submit()`` to the first token out of the
  stream iterator; a failed request counts as never answered.

The same three numbers are printed for each of the window's sub-windows of
about ``SUBWINDOW_S`` seconds (never fewer than two): whether runs differ
inside themselves, which a longer window averages, or process from process,
which it does not.  A traced run also counts, from the clients' records alone,
the work the traced window asked of the model (``traced_work``), for the
readers of the kernel's roofline share and of the step's share of the peak.

When the window closes the clients stop submitting and the engine drains:
every request submitted inside the window is ``attempted`` and has
``drain_s`` to end.  A request fails if it raises, delivers another number of
tokens than asked, or is still running when the drain's time is up (it is
then cancelled so the process can end).
After that the reference runs one causal forward over each sampled finished
request (prompt + served tokens); how far the served tokens' logits lie
below the reference's best, on average and at the widest, decides
``correct``.
"""

from __future__ import annotations

import statistics
import threading
import time

import numpy as np

from perfbench.harness.runner import peak_bytes
from perfbench.harness.stats import median, quantile


def grid(spec, n):
    """``n`` values of one distribution at evenly spaced quantiles."""
    qs = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        vals = spec["min"] + qs * (spec["max"] + 1 - spec["min"])
    elif spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(q) for q in qs])
        vals = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    else:
        raise SystemExit(f"perfbench: unknown distribution {spec['dist']!r}")
    return np.clip(np.floor(vals), spec["min"], spec["max"]).astype(int)


def request_sizes(traffic):
    """The mix's fixed sequence of (prompt, new tokens) sizes: the pairing
    and the order are the mix's (``grid_seed``), the same for every seed.
    The seed draws the token ids and the weights only: in a closed loop the
    order decides which sizes meet in the engine, and with it the rate and
    the queue, so runs whose seeds ordered the sizes differed by 3 % in
    tokens per second and 11 % in the time to a first token where two runs
    of one order agree within 1 % and 2.5 % (my chip runs, PR 23)."""
    n = int(traffic["size_grid"])
    prompts = grid(traffic["prompt_len"], n)
    news = grid(traffic["new_tokens"], n)
    rng = np.random.default_rng(int(traffic["grid_seed"]))
    news = news[rng.permutation(n)]
    return [(int(prompts[i]), int(news[i])) for i in rng.permutation(n)]


def prompt_tokens(vocab, seed, k, length):
    """Request ``k``'s prompt: tokens of its own, from the seed."""
    return np.random.default_rng([int(seed), 4, k]).integers(
        1, vocab, length).astype(np.int32)


class _Request:
    __slots__ = ("k", "prompt", "want", "t_submit", "t_tokens", "tokens",
                 "t_end", "error", "cut")

    def __init__(self, k, prompt, want):
        self.k, self.prompt, self.want = k, prompt, want
        self.t_submit = self.t_end = None
        self.t_tokens, self.tokens = [], []
        self.error, self.cut = None, False


class _Load:
    """The closed loop: shared request counter, one thread per client."""

    def __init__(self, engine, traffic, vocab, seed):
        self.engine, self.traffic, self.vocab, self.seed = (
            engine, traffic, vocab, seed)
        self.sizes = request_sizes(traffic)
        self.lock = threading.Lock()
        self.next_k = 0
        self.stop = threading.Event()
        self.requests = []          # every request, in submit order
        self.live = {}              # client -> (request, stream)
        self.late = []              # stream end -> next submit, seconds
        self.threads = [threading.Thread(target=self._client, args=(i,),
                                         name=f"pb-client-{i}", daemon=True)
                        for i in range(int(traffic["clients"]))]

    def _client(self, i):
        last_end = None
        while not self.stop.is_set():
            with self.lock:
                k = self.next_k
                self.next_k += 1
            plen, want = self.sizes[k % len(self.sizes)]
            req = _Request(k, prompt_tokens(self.vocab, self.seed, k, plen),
                           want)
            if self.stop.is_set():
                return
            req.t_submit = time.perf_counter()
            if last_end is not None:
                self.late.append(req.t_submit - last_end)
            try:
                stream = self.engine.submit(req.prompt, max_new_tokens=want)
                with self.lock:
                    self.requests.append(req)
                    self.live[i] = (req, stream)
                for tok in stream:
                    req.t_tokens.append(time.perf_counter())
                    req.tokens.append(int(tok))
            except BaseException as e:  # noqa: BLE001 — a failed request is
                # a result of the run, counted, never the end of the run
                if not req.cut:
                    req.error = repr(e)
                if req not in self.requests:
                    with self.lock:
                        self.requests.append(req)
            req.t_end = last_end = time.perf_counter()

    def start(self):
        for t in self.threads:
            t.start()

    def drain(self, seconds):
        """Stop submitting, give what runs ``seconds`` to end, cancel the
        rest; returns the number of client threads that did not end."""
        self.stop.set()
        until = time.perf_counter() + seconds
        for t in self.threads:
            t.join(timeout=max(0.0, until - time.perf_counter()))
        with self.lock:
            live = list(self.live.values())
        for req, stream in live:
            if req.t_end is None:
                req.cut = True
                stream.cancel()
        for t in self.threads:
            t.join(timeout=60)
        return sum(t.is_alive() for t in self.threads)


def measure(load, t0, t1):
    """The window's numbers from the clients' records."""
    inside = [r for r in load.requests if t0 <= r.t_submit < t1]
    tokens = sum(1 for r in load.requests for t in r.t_tokens if t0 <= t < t1)
    gaps = [b - a for r in load.requests
            for a, b in zip(r.t_tokens, r.t_tokens[1:]) if t0 <= b < t1]
    failed = [r for r in inside
              if r.error or r.cut or len(r.tokens) != r.want]
    bad = {id(r) for r in failed}
    ttft = [float("inf") if id(r) in bad else r.t_tokens[0] - r.t_submit
            for r in inside]
    finished = [r for r in load.requests
                if not r.cut and not r.error and r.t_end is not None
                and t0 <= r.t_end < t1]
    return {"inside": inside, "tokens": tokens, "gaps": gaps,
            "failed": failed, "ttft": ttft, "finished": finished}


SUBWINDOW_S = 10.0


def window_numbers(m, seconds):
    """The three end-to-end numbers of ``measure``'s records."""
    nan = float("nan")
    return {"serve_tokens_per_s": m["tokens"] / seconds,
            "ttft_p95_ms": 1e3 * (quantile(m["ttft"], 0.95) or nan),
            "itl_p95_ms": 1e3 * (quantile(m["gaps"], 0.95) or nan)}


def subwindows(load, t0, t1):
    """``[(from_s, to_s, numbers, ttft samples, gaps)]`` for the window cut
    into equal parts of about ``SUBWINDOW_S`` seconds, two at the least."""
    n = max(2, round((t1 - t0) / SUBWINDOW_S))
    edges = [t0 + (t1 - t0) * i / n for i in range(n)] + [t1]
    out = []
    for a, b in zip(edges, edges[1:]):
        m = measure(load, a, b)
        out.append((a - t0, b - t0, window_numbers(m, b - a),
                    len(m["ttft"]), len(m["gaps"])))
    return out


def traced_work(load, t0, t1):
    """What the traffic asked of the model between ``t0`` and ``t1``, from
    the clients' records alone.  A stream's token ``j >= 1`` comes out of a
    decode step that attends over the prompt and the ``j`` tokens before it;
    its token 0 comes out of the prompt's prefill."""
    decode_tokens = live_positions = 0
    prompt_lens = []
    for r in load.requests:
        for j, t in enumerate(r.t_tokens):
            if not t0 <= t < t1:
                continue
            if j == 0:
                prompt_lens.append(len(r.prompt))
            else:
                decode_tokens += 1
                live_positions += len(r.prompt) + j
    return {"decode_tokens": decode_tokens,
            "live_positions": live_positions, "prompt_lens": prompt_lens}


def sample_finished(finished, n, seed):
    """``n`` finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in finished if r is not longest]
    pick = np.random.default_rng([int(seed), 5]).permutation(len(rest))
    return [longest] + [rest[i] for i in pick[:max(0, n - 1)]]


def gap_numbers(gaps):
    """(widest, mean) over all compared positions of how far a token's
    reference logit lies below the reference's best.  The widest gap is held
    against a token from a wrong cache row or position; the mean is what
    separates precisions: flips happen where the two best logits lie within
    the arithmetic's noise, so their number and their size both grow with it
    and the mean grows with its square, while a widest gap swings."""
    if not gaps:
        return None, None
    flat = np.concatenate(gaps)
    return float(flat.max()), float(flat.mean())


def run(ctx, devs):
    import jax

    import flexflow_tpu as ff
    from flexflow_tpu import fflogger
    from flexflow_tpu.obs.trace import get_tracer

    cell, tr = ctx.cell, dict(ctx.cell.traffic)
    if ctx.trace:
        tr["program_args"] = list(tr["program_args"]) + list(
            tr["program_args_traced"])
    fam = cell.module("families", cell.config["family"])
    ref = cell.module("reference", fam.REFERENCE)
    sz = fam.sizes(cell.config)
    ctx.mark("imports")
    model = fam.build_serve(cell.config, tr)
    fam.install(model, sz, ref.init_params(sz, ctx.seed))
    ctx.mark("model built, weights installed")
    slots = int(tr["slots"])
    with fflogger.silenced("serve"):
        engine = ff.GenerationEngine(
            model, slots=slots, max_new_tokens=int(tr["new_tokens"]["max"]))
        engine.start()      # the program's own warm-up: every bucket + decode
        ctx.say(f"engine up at {time.perf_counter() - ctx.t_start:.2f} s: "
                f"slots {slots}, kv pool {engine.kv_cache_bytes / 1e9:.3f} GB "
                f"in {engine.num_pages} pages, clients {tr['clients']}")
        load = _Load(engine, tr, sz["vocab"], ctx.seed)
        load.start()
        time.sleep(float(tr["warm_s"]))
        t0 = ctx.open_window()
        t0_mono = time.monotonic()
        t1 = t0 + ctx.seconds
        if ctx.trace:
            time.sleep(float(tr["trace_after_s"]))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(ctx.trace_dir, profiler_options=opts)
            t_annotated = time.perf_counter()
            with jax.profiler.TraceAnnotation("pb.traced_window"):
                time.sleep(float(tr["trace_s"]))
            t_traced = (t_annotated, time.perf_counter())
            jax.profiler.stop_trace()
        time.sleep(max(0.0, t1 - time.perf_counter()))
        stuck = load.drain(float(tr["drain_s"]))
        peak = peak_bytes(devs)
        stats = engine.stats()
        engine.stop()
    spans = [s for s in get_tracer().snapshot()["spans"]
             if t0_mono <= s["t0_ns"] / 1e9 and
             s["t1_ns"] / 1e9 <= t0_mono + ctx.seconds]
    m = measure(load, t0, t1)
    ctx.counters.update(slots=slots,
                        prefix_hit_tokens=stats.get("prefix_hit_tokens"))
    late = load.late or [0.0]
    ctx.say(f"window {ctx.seconds:.3f} s: {len(m['inside'])} requests "
            f"submitted, {len(m['finished'])} finished, {len(m['failed'])} "
            f"failed, {sum(r.cut for r in load.requests)} cancelled after "
            f"the drain; "
            f"{m['tokens']} tokens, {len(m['ttft'])} ttft samples, "
            f"{len(m['gaps'])} gaps; ttft p50 "
            f"{1e3 * (median(m['ttft']) or 0):.2f} ms p95 "
            f"{1e3 * (quantile(m['ttft'], 0.95) or 0):.2f} ms, gap p50 "
            f"{1e3 * (median(m['gaps']) or 0):.2f} ms; closed loop late by "
            f"p50 {1e3 * median(late):.3f} ms max {1e3 * max(late):.3f} ms; "
            f"client threads still alive {stuck}")
    for a, b, sub, n_ttft, n_gaps in subwindows(load, t0, t1):
        ctx.say(f"sub-window {a:.2f}-{b:.2f} s: " + ", ".join(
            f"{k} {v!r}" for k, v in sub.items())
            + f" ({n_ttft} ttft samples, {n_gaps} gaps)")
    e2e = window_numbers(m, ctx.seconds)
    ctx.counters["ttft_ms"] = [1e3 * t for t in m["ttft"]]
    if ctx.trace:
        ctx.counters["traced_work"] = traced_work(load, *t_traced)

    # ---- the comparison, outside the window and outside set-up ---------
    del engine, model
    t_ref = time.perf_counter()
    sample = sample_finished(m["finished"], int(tr["compared_requests"]),
                             ctx.seed)
    gaps = ref.served_gaps(sz, ctx.seed, [(r.prompt, r.tokens)
                                          for r in sample], "float32")
    widest, mean = gap_numbers([g["served"] for g in gaps])
    wrong_len = sum(len(r.tokens) != r.want for r in m["finished"])
    ctx.say(f"reference: {len(sample)} finished requests, "
            f"{sum(len(r.tokens) for r in sample)} served tokens, in "
            f"{time.perf_counter() - t_ref:.2f} s (not in setup_s)")
    lim = cell.doc["limits"]
    numbers = [("served_gap_mean", mean, lim["served_gap_mean"]),
               ("served_gap_widest", widest, lim["served_gap_widest"]),
               ("finished_with_wrong_length", float(wrong_len), 0.0),
               ("client_threads_stuck", float(stuck), 0.0)]
    return {"end_to_end": e2e, "attempted": len(m["inside"]),
            "failed": len(m["failed"]), "numbers": numbers, "spans": spans,
            "memory_peak_bytes": peak}
