"""FFConfig / ParallelConfig — run configuration and the strategy atom.

TPU-native re-design of the reference's ``include/config.h`` (FFConfig,
ParallelConfig; defaults in ``src/runtime/model.cc:1182-1219``; CLI parser
``model.cc:1221-1289``).  The reference counts CUDA GPUs per node
(``-ll:gpu``); here the worker unit is a TPU chip in a ``jax`` device mesh
(``-ll:tpu``, with ``-ll:gpu`` accepted as a compatibility alias).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Dict, List, Optional, Sequence, Tuple

MAX_TENSOR_DIM = 4  # logical graph dims, matching reference config.h:30
MAX_SEQ_DIM = 1


class DeviceType(enum.IntEnum):
    """Mirrors strategy.proto's Op.DeviceType (GPU=0, CPU=1).

    On TPU the accelerator slot is the TPU chip; ``DEVICE`` keeps the
    wire-format value 0 so existing strategy files parse unchanged.  ``HOST``
    (=CPU) marks ops placed on the host — the reference uses this for DLRM
    embedding tables (``dlrm_strategy_hetero.cc``); we map it to host-memory
    offload.
    """

    DEVICE = 0  # accelerator (TPU chip); reference: GPU
    HOST = 1    # host CPU

    # aliases for reference-parity spelling
    GPU = 0
    CPU = 1
    TPU = 0


class MemoryType(enum.IntEnum):
    """Mirrors strategy.proto Op.MemoryType: FBM (device HBM) / ZCM (host)."""

    FBM = 0  # device framebuffer -> TPU HBM
    ZCM = 1  # zero-copy (host-pinned) -> host memory


# The per-op precision axis of the SOAP space (ISSUE 14): a strategy may
# pin one op's compute dtype independently of FFConfig.compute_dtype.
# "" = follow the run's global compute dtype (the backward-compatible
# default every shipped .pb reads as); "bf16"/"f32" force the op.  Wire
# values in strategy.proto field 6: 0 = follow, 1 = bf16, 2 = f32.
PRECISIONS = ("", "bf16", "f32")
# precision token -> jnp dtype name (the "" default resolves to the
# session dtype at the ONE trace-time resolution point, ops/common.py)
PRECISION_DTYPES = {"bf16": "bfloat16", "f32": "float32"}
# dtype names FFConfig.compute_dtype / param_dtype may take — validated
# at construction so a typo fails with the field name, not deep inside
# jnp.dtype at trace time
VALID_COMPUTE_DTYPES = ("bfloat16", "float32", "float16")
VALID_PARAM_DTYPES = ("float32", "bfloat16", "float64")


def _validate_dtype_field(field: str, value: str, allowed) -> None:
    if value not in allowed:
        raise ValueError(
            f"FFConfig.{field} must be one of {', '.join(allowed)}, got "
            f"{value!r}")


def dtype_short(dtype_name: str) -> str:
    """The ONE dtype -> short-tag spelling ("bfloat16" -> "bf16") that
    ``precision_policy`` tags are written in."""
    return {"bfloat16": "bf16", "float32": "f32",
            "float16": "f16"}.get(dtype_name, dtype_name)


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """The SOAP strategy atom (reference ``config.h:42-51``).

    ``dims[i]`` is the partition degree of logical tensor dim ``i`` of the
    op's *output* tensor, ordered outermost-first (sample dim first) —
    note the reference stores ``adim`` innermost-first; we use natural
    (row-major, sample-major) order throughout and convert at the strategy
    file boundary.

    ``device_ids`` enumerates the flat mesh coordinates owning each part
    (row-major over ``dims``).  On TPU, device ids index into the flattened
    ``jax`` device mesh rather than Legion processor lists.
    """

    device_type: DeviceType = DeviceType.DEVICE
    dims: Tuple[int, ...] = (1,)
    device_ids: Tuple[int, ...] = (0,)
    memory_types: Tuple[MemoryType, ...] = ()
    # per-op precision (the SOAP precision axis, ISSUE 14): "" follows
    # FFConfig.compute_dtype — the default every pre-existing strategy
    # (and every shipped .pb, which has no field 6) resolves to, so the
    # default policy is bit-identical to a build without the axis.
    precision: str = ""

    def __post_init__(self):
        if self.precision not in PRECISIONS:
            raise ValueError(
                f"ParallelConfig.precision must be one of "
                f"{PRECISIONS}, got {self.precision!r}")

    @property
    def num_parts(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n

    def with_dims(self, dims: Sequence[int]) -> "ParallelConfig":
        nparts = 1
        for d in dims:
            nparts *= d
        return ParallelConfig(
            device_type=self.device_type,
            dims=tuple(int(d) for d in dims),
            device_ids=tuple(range(nparts)),
            memory_types=self.memory_types,
            precision=self.precision,
        )

    @staticmethod
    def data_parallel(num_parts: int, ndims: int = 2) -> "ParallelConfig":
        """Reference ``Op::get_data_parallel_config`` (model.cc:263-274):
        partition only the sample (outermost) dim."""
        dims = (num_parts,) + (1,) * (ndims - 1)
        return ParallelConfig(
            device_type=DeviceType.DEVICE,
            dims=dims,
            device_ids=tuple(range(num_parts)),
        )


class CompMode(enum.Enum):
    TRAINING = "training"
    INFERENCE = "inference"


@dataclasses.dataclass
class FFConfig:
    """Run configuration (reference ``config.h:66-103``).

    Reference defaults from ``model.cc:1182-1197``: epochs=1, batchSize=64,
    lr=0.01, wd=0.0001, workersPerNode=0, numNodes=1, search_budget=0,
    search_alpha=0.05, profiling off.
    """

    epochs: int = 1
    batch_size: int = 64
    learning_rate: float = 0.01
    weight_decay: float = 1e-4
    workers_per_node: int = 0   # -ll:tpu — chips per host; 0 = all visible
    cpus_per_node: int = 1      # -ll:cpu
    num_nodes: int = 1          # --nodes
    profiling: bool = False
    # -p/--print-freq: epochs between metric prints in fit().  The
    # reference parses printFreq (model.cc:1223-1226) into config.h:85 but
    # never reads it; here it actually gates the epoch line.
    print_frequency: int = 1
    # strategy search knobs (reference model.cc:1253-1260)
    search_budget: int = 0      # --budget: MCMC iterations
    search_alpha: float = 0.05  # --alpha: annealing temperature
    search_chains: int = 1      # --chains: independent MCMC chains
    search_overlap_backward_update: bool = False
    # --search-precision: grow the SOAP space with the per-op precision
    # axis (ISSUE 14) — MCMC proposals may flip one op between bf16 and
    # f32 (loss/norm-statistics ops stay pinned f32 by the FF140
    # legality pass) alongside partitioning mutations, and the cost
    # model charges dtype-dependent compute rate + HBM traffic per op.
    # OFF by default: the proposal distribution (and therefore every
    # acceptance decision) is bit-identical to a build without the axis.
    search_precision: bool = False
    # --search-mode: "mcmc" (the pure anneal, the historical default —
    # fixed-seed bit-identical across releases) or "hybrid" (ISSUE 20:
    # exact DP over decomposable subgraphs + cost-guided MCMC on the
    # residual cross-region variables, docs/strategy_search.md "Exact
    # DP on decomposable subgraphs")
    search_mode: str = "mcmc"
    # --best-known: on-disk BestStrategyStore JSON for warm-started
    # transfer — seeds the search from the best prior strategy recorded
    # for the same graph digest/device count/estimator, and records the
    # winner back when it improves on the stored entry
    best_known_file: str = ""
    # --reshard-budget: MCMC iterations for the IN-THE-LOOP re-search an
    # elastic reshard point runs (FFModel.reshard / reshard-on-resume,
    # docs/elastic.md "Resharding").  None = reuse search_budget; the
    # delta-sim SimSession makes even the full budget cheap, but a
    # reshard pause is latency the training loop feels, so this can be
    # dialed down independently.  0 disables re-search at reshard points
    # (strategies rescale onto the new mesh's data axis instead).
    reshard_search_budget: Optional[int] = None
    import_strategy_file: str = ""
    export_strategy_file: str = ""
    # TPU-native additions
    dataset_path: str = ""
    seed: int = 0
    compute_dtype: str = "bfloat16"  # MXU-native compute dtype
    param_dtype: str = "float32"
    mesh_shape: Optional[Dict[str, int]] = None  # explicit mesh override
    simulator_mode: str = "analytic"  # "analytic" | "measure"
    # Profile-calibrated cost model (search/calibration.py,
    # docs/strategy_search.md "Calibration").  calibration_file points at
    # a CalibrationTable JSON harvested by `flexflow-tpu calibrate`;
    # cost_estimator picks the per-op time model the simulator searches
    # with: "analytic" (the raw roofline), "table" (roofline rescaled by
    # measured/analytic ratios), "ridge" (learned regression over op
    # features, arXiv 2008.01040), or "auto" (= "table" when a file is
    # set, "analytic" otherwise).  With no file and the default "auto",
    # nothing is loaded and every simulator output is bit-identical to
    # an uncalibrated build.
    calibration_file: str = ""
    cost_estimator: str = "auto"  # auto | analytic | table | ridge
    remat: bool = False  # jax.checkpoint the forward pass
    # internal conv/pool layout: "nchw" (reference parity), "nhwc"
    # (channels-minor = TPU lane dim), or "auto" (currently nchw until the
    # on-chip A/B lands — flip after measurement, see BASELINE.md)
    conv_layout: str = "auto"
    # Pallas flash-attention kernel.  None = auto: flash at s >= 1024
    # (measured on v5e: flash 2.7-2.8x faster at s=1024..3072, only
    # source of attention at s >= 8192 where the dense f32 score matrix
    # exceeds HBM; XLA's fused dense attention wins below s=1024 — see
    # BASELINE.md "Flash attention").  True/False force the choice.
    flash_attention: Optional[bool] = None
    # when set, fit() wraps the epoch loop in a jax.profiler trace whose
    # dump lands here (TensorBoard-loadable) — the XLA-level complement of
    # --profiling's per-op table
    trace_dir: str = ""
    # Observability plane (flexflow_tpu/obs, docs/observability.md).
    # trace_sample_rate: fraction of submit()/fit() requests that get a
    # request-scoped span trace (0 = tracing fully off — the hot path
    # pays one lock-free boolean check per dispatch; 1.0 = every
    # request, deterministic systematic sampling, no RNG).  Export the
    # recorded spans with `flexflow-tpu trace export`.
    trace_sample_rate: float = 0.0
    # metrics_port: serve the process metrics registry's Prometheus
    # text exposition on GET /metrics at this port (stdlib HTTP, daemon
    # thread; 0 = no endpoint).  The registry backs the
    # serve_stats/gen_stats events, so the scrape and the event stream
    # cannot diverge.  metrics_host defaults to LOOPBACK — the
    # exposition names tenants and their traffic; binding a routable
    # interface ("0.0.0.0" for a cluster scraper) is an explicit
    # choice via --metrics-host.
    metrics_port: int = 0
    metrics_host: str = "127.0.0.1"
    # Gradient accumulation: split each batch into k equal microbatches
    # inside the ONE jitted train step (lax.scan), accumulate grads, and
    # apply a single optimizer update — activation memory scales with
    # the microbatch while the effective batch stays cfg.batch_size.
    # Equivalent to the full-batch step for deterministic forwards under
    # both mean- and sum-reduced losses (loss/metric sums exact with
    # equal microbatch sizes).  Caveats: dropout draws a fresh mask per
    # microbatch (a DIFFERENT, equally valid realization than one
    # full-batch mask), and batchnorm running stats take the LAST
    # microbatch's measurement once per step.  batch_size must divide
    # by k (checked at compile()).
    gradient_accumulation_steps: int = 1
    # Fused multi-step dispatch: fit() stages windows of K device-resident
    # batches and executes ONE jitted donated lax.scan over the K train
    # steps, so per-step host work (Python dispatch, eager _repin_host
    # transfers, callbacks bookkeeping) is paid once per WINDOW instead of
    # once per step — the TPU-native analogue of the reference's Legion
    # index launches over the batch partition
    # (flexflow_dataloader.cc:260-330).  K=1 keeps the current
    # one-dispatch-per-step behavior bit-exactly.  Semantics at K>1
    # (docs/performance.md "Fused multi-step dispatch"):
    #   * params/opt_state are threaded and donated across the window;
    #     per-step losses and metric sums accumulate on device and are
    #     fetched once per epoch;
    #   * faults.on_step indices round UP to the window edge (a
    #     kill_at_step:5 under K=4 fires after step 8 — the elastic
    #     recovery matrix stays honest, tests/test_faults.py);
    #   * checkpoint cadence (ModelCheckpoint / save_checkpoint in
    #     callbacks) is window-aligned: epoch boundaries always are;
    #   * composes with gradient_accumulation_steps (the accumulation
    #     scan nests INSIDE each step of the window scan).
    steps_per_dispatch: int = 1
    # Opt-in padded-tail training: fit() consumes the tail samples that do
    # not fill a whole batch (PrefetchLoader pads them to batch_size and
    # the train step masks the padding out of loss/metrics/grads) instead
    # of silently dropping them.  The masked step is mathematically the
    # mean/sum over the VALID rows only; batchnorm running stats and
    # per-microbatch dropout masks still see the padded rows (documented
    # caveat, like gradient accumulation's batchnorm note above).
    pad_tail_batches: bool = False
    # Serving engine knobs (flexflow_tpu/serving, docs/serving.md).
    # serve_max_batch: largest packed micro-batch the inference engine
    # dispatches (0 = batch_size); also the largest shape bucket, so the
    # AOT warmup compiles every bucket up to it at startup.
    serve_max_batch: int = 0
    # serve_max_wait_ms: micro-batcher coalescing deadline — a pending
    # request is dispatched no later than this many ms after it was
    # submitted, even if the batch is not full (latency floor under
    # light load; under heavy load batches fill before the deadline).
    serve_max_wait_ms: float = 2.0
    # serve_max_queue_rows: bounded-queue admission control (docs/
    # serving.md "Overload, SLOs & degradation").  0 = unbounded (the
    # fair-weather default: nothing is ever rejected/shed, the
    # un-overloaded path is bit-identical to an engine without
    # admission control).  > 0 bounds the micro-batcher's pending rows;
    # serve_admission picks what happens to a submit() that would
    # overflow it: "block" (wait for room — backpressure), "reject"
    # (fail fast with OverloadError, nothing queued) or "shed_oldest"
    # (evict the oldest queued request of the lowest priority class not
    # above the incoming one, failing it with SheddedError).
    serve_max_queue_rows: int = 0
    serve_admission: str = "block"
    # serve_starvation_ms: anti-starvation aging bound for priority
    # classes — a queued request older than this jumps the priority
    # order, so sustained high-priority load delays low-priority work
    # but can never starve it.  0 disables aging (strict priority).
    serve_starvation_ms: float = 250.0
    # serve_model_name: the tenant identity serving engines stamp on
    # their serve_stats/gen_stats/serve_health events (docs/serving.md
    # "Model fleets").  In a multi-model process (FleetEngine) every
    # tenant gets its registry name automatically; set this for a
    # single-engine deployment whose event stream will be merged with
    # others' ("" = untagged single-engine default).
    serve_model_name: str = ""
    # serve_quantize: weight quantization for the serving bucket
    # executables (docs/serving.md "Int8 weight quantization").  "" =
    # off (the default — serving params, executables and results are
    # bit-identical to a build without quantization); "int8" =
    # per-output-channel symmetric int8 weight-only quantization of the
    # eligible matmul kernels (FFModel.quantize_weights), dequant fused
    # into the matmul, with a max-abs-error quality bound checked at
    # engine warmup.  Halves-to-quarters the weights' HBM residency and
    # bandwidth; the fleet gate's resident_bytes accounting follows
    # byte-for-byte.
    serve_quantize: str = ""
    # serve_buckets: explicit comma-separated batch buckets ("2,4,16,64");
    # empty = powers of two 2,4,...,serve_max_batch (the default omits
    # bucket 1 to keep results packing-invariant — single-row programs
    # hit matrix-vector kernels whose bits differ ~1 ulp; opt in via an
    # explicit list, see serving/batcher.derive_buckets).  Each bucket
    # is lowered + AOT-compiled once at engine startup
    # (FFModel.forward_compiled) and reused for every packed batch.
    serve_buckets: str = ""
    # Token-generation serving (flexflow_tpu/serving/generation,
    # docs/serving.md "Token generation").  serve_gen_slots: width of
    # the continuous-batching decode batch — the number of concurrent
    # streams sharing one KV cache and one decode dispatch per step
    # (>= 2: a 1-slot decode lowers matrix-vector kernels and breaks
    # the decode==forward parity pin, like serve_buckets' floor).
    serve_gen_slots: int = 8
    # serve_gen_max_seq: per-slot KV-cache length (prompt + generated
    # tokens); 0 = the model's input sequence length.  Drives the
    # preallocated HBM the FF108/FF121 gates account with
    # `lint --serve-slots` (analysis/kv_memory.py).
    serve_gen_max_seq: int = 0
    # serve_gen_max_new_tokens: default generation budget per request
    # when submit() does not specify one.
    serve_gen_max_new_tokens: int = 32
    # Paged KV cache (docs/serving.md "Paged KV & prefix caching").
    # serve_kv_page: tokens per KV page — the sharing/allocation
    # granularity of the generation engine's page pool (and the prefix
    # cache's match granularity: only full pages are shareable).
    serve_kv_page: int = 16
    # serve_kv_pages: total pool pages; 0 = auto, the dense worst case
    # slots x ceil(max_seq / page) so the accounting equals the old
    # dense preallocation (analysis/kv_memory.py) — shrink it once the
    # engine's kv_pages_high_water says so.  Undersized pools shed
    # streams (KVCacheExhausted) after LRU-evicting cached prefixes.
    serve_kv_pages: int = 0
    # serve_prefix_cache: "on" (default) caches full pages of prompt
    # prefixes in a ref-counted trie so shared system prompts skip
    # their prefill; "off" disables it — tokens are bit-identical
    # either way (the ISSUE 15 correctness anchor), only TTFT and
    # pages-in-use change.
    serve_prefix_cache: str = "on"
    # serve_prefill_chunk: prefill long prompts in chunks of this many
    # tokens, at most one chunk per decode-step boundary, capping the
    # decode stall a joining prompt inflicts on in-flight streams
    # (Sarathi-style).  0 = whole-prompt chunks (monolithic prefill).
    serve_prefill_chunk: int = 0
    # Speculative decoding (docs/serving.md "Speculative decoding &
    # sampling").  serve_spec_gamma: draft tokens proposed per round
    # when a draft model is attached — 0 = off, else >= 2 (a 1-row
    # verify window lowers matrix-vector kernels whose bits drift,
    # same floor as serve_gen_slots/serve_buckets).  Only consulted
    # when the engine is given a draft model.
    serve_spec_gamma: int = 0
    # serve_spec_gamma_max: ceiling for the adaptive controller's γ
    # candidates (and a sanity bound for the fixed policy).
    serve_spec_gamma_max: int = 4
    # serve_spec_policy: "fixed" runs serve_spec_gamma every round;
    # "adaptive" prices candidate γs from the live accept-rate EWMA
    # against their calibrated round cost and retunes periodically.
    serve_spec_policy: str = "fixed"
    # Sparse embedding-table updates (reference parity: the embedding
    # backward scatter-accumulates only the touched rows,
    # embedding.cu:192-228 — it never streams the full table).  A dense
    # jax autodiff update instead materializes a table-shaped gradient
    # and the optimizer rewrites every row: ~4 full-table HBM passes per
    # step, which dominates DLRM-class models.  "auto" = use the sparse
    # path (autodiff w.r.t. the gathered rows + scatter-add update, an
    # EXACT rewrite of plain-SGD) whenever the optimizer is SGD with
    # momentum=0/weight_decay=0, the table is device-placed, unshared,
    # and the id tensor is a graph input; True forces eligible tables,
    # False disables.
    sparse_embedding_updates: Optional[bool] = None  # None = auto

    # resolved at FFModel construction
    strategies: Dict[str, ParallelConfig] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        # fail at construction with the FIELD name — an unknown dtype
        # string used to surface as an opaque jnp.dtype error deep
        # inside the first trace (ISSUE 14 satellite)
        _validate_dtype_field("compute_dtype", self.compute_dtype,
                              VALID_COMPUTE_DTYPES)
        _validate_dtype_field("param_dtype", self.param_dtype,
                              VALID_PARAM_DTYPES)
        if self.serve_quantize not in ("", "int8"):
            raise ValueError(
                f"FFConfig.serve_quantize must be '' or 'int8', got "
                f"{self.serve_quantize!r}")
        if self.serve_prefix_cache not in ("on", "off"):
            raise ValueError(
                f"FFConfig.serve_prefix_cache must be 'on' or 'off', "
                f"got {self.serve_prefix_cache!r}")
        if self.serve_kv_page < 1:
            raise ValueError(
                f"FFConfig.serve_kv_page must be >= 1, got "
                f"{self.serve_kv_page}")
        if self.serve_kv_pages < 0 or self.serve_prefill_chunk < 0:
            raise ValueError(
                f"FFConfig.serve_kv_pages/serve_prefill_chunk must be "
                f">= 0 (0 = auto/monolithic), got "
                f"{self.serve_kv_pages}/{self.serve_prefill_chunk}")
        if self.serve_spec_gamma != 0 and self.serve_spec_gamma < 2:
            raise ValueError(
                f"FFConfig.serve_spec_gamma must be 0 (off) or >= 2, "
                f"got {self.serve_spec_gamma}")
        if self.serve_spec_gamma_max < 2:
            raise ValueError(
                f"FFConfig.serve_spec_gamma_max must be >= 2, got "
                f"{self.serve_spec_gamma_max}")
        if self.serve_spec_policy not in ("fixed", "adaptive"):
            raise ValueError(
                f"FFConfig.serve_spec_policy must be 'fixed' or "
                f"'adaptive', got {self.serve_spec_policy!r}")

    @property
    def num_devices(self) -> int:
        return max(1, self.workers_per_node) * self.num_nodes

    def precision_policy(self) -> str:
        """Short human-readable tag of the run's precision policy: the
        global compute dtype ("bf16"/"f32"/...), "+mixed(B/F)" when per-op
        strategy overrides are present (B ops bf16, F ops f32), and
        "+int8w" under serving weight quantization."""
        short = dtype_short(self.compute_dtype)
        nb = sum(1 for pc in self.strategies.values()
                 if pc is not None and pc.precision == "bf16")
        nf = sum(1 for pc in self.strategies.values()
                 if pc is not None and pc.precision == "f32")
        if nb or nf:
            short += f"+mixed({nb}bf16/{nf}f32)"
        if self.serve_quantize:
            short += f"+{self.serve_quantize}w"
        return short

    @staticmethod
    def parse_args(argv: Optional[List[str]] = None) -> "FFConfig":
        """CLI parser with the reference's flag set (model.cc:1221-1289):
        ``-e/--epochs -b/--batch-size --lr/--learning-rate --wd/--weight-decay
        -p/--print-freq -d/--dataset --budget --alpha -s/--export -import/
        --import -ll:tpu -ll:gpu -ll:cpu --nodes --profiling --overlap``."""
        import sys

        if argv is None:
            argv = sys.argv[1:]
        cfg = FFConfig()
        i = 0
        while i < len(argv):
            a = argv[i]

            def val() -> str:
                nonlocal i
                i += 1
                return argv[i]

            if a in ("-e", "--epochs"):
                cfg.epochs = int(val())
            elif a in ("-b", "--batch-size"):
                cfg.batch_size = int(val())
            elif a in ("--lr", "--learning-rate"):
                cfg.learning_rate = float(val())
            elif a in ("--wd", "--weight-decay"):
                cfg.weight_decay = float(val())
            elif a in ("-p", "--print-freq"):
                cfg.print_frequency = max(1, int(val()))
            elif a in ("-d", "--dataset"):
                cfg.dataset_path = val()
            elif a == "--budget":
                cfg.search_budget = int(val())
            elif a == "--alpha":
                cfg.search_alpha = float(val())
            elif a == "--chains":
                cfg.search_chains = max(1, int(val()))
            elif a == "--search-precision":
                cfg.search_precision = True
            elif a == "--search-mode":
                mode = val().lower()
                if mode not in ("mcmc", "hybrid"):
                    raise ValueError(
                        f"--search-mode {mode!r}: want 'mcmc' or 'hybrid'")
                cfg.search_mode = mode
            elif a == "--best-known":
                cfg.best_known_file = val()
            elif a == "--reshard-budget":
                cfg.reshard_search_budget = int(val())
            elif a == "--calibration":
                cfg.calibration_file = val()
            elif a == "--cost-estimator":
                cfg.cost_estimator = val().lower()
            elif a == "--overlap":
                cfg.search_overlap_backward_update = True
            elif a in ("-s", "--export"):
                cfg.export_strategy_file = val()
            elif a in ("-import", "--import"):
                cfg.import_strategy_file = val()
            elif a in ("-ll:tpu", "-ll:gpu"):
                cfg.workers_per_node = int(val())
            elif a == "-ll:cpu":
                cfg.cpus_per_node = int(val())
            elif a == "--nodes":
                cfg.num_nodes = int(val())
            elif a == "--profiling":
                cfg.profiling = True
            elif a == "--seed":
                cfg.seed = int(val())
            elif a == "--remat":
                cfg.remat = True
            elif a == "--conv-layout":
                cfg.conv_layout = val().lower()
            elif a == "--accum-steps":
                cfg.gradient_accumulation_steps = int(val())
            elif a == "--steps-per-dispatch":
                cfg.steps_per_dispatch = int(val())
            elif a == "--pad-tail":
                cfg.pad_tail_batches = True
            elif a == "--serve-max-batch":
                cfg.serve_max_batch = int(val())
            elif a == "--serve-max-wait-ms":
                cfg.serve_max_wait_ms = float(val())
            elif a == "--serve-buckets":
                cfg.serve_buckets = val()
            elif a == "--serve-quantize":
                cfg.serve_quantize = val().lower()
                if cfg.serve_quantize not in ("", "int8"):
                    raise ValueError(
                        f"--serve-quantize must be '' or 'int8', got "
                        f"{cfg.serve_quantize!r}")
            elif a == "--compute-dtype":
                cfg.compute_dtype = val().lower()
                _validate_dtype_field("compute_dtype", cfg.compute_dtype,
                                      VALID_COMPUTE_DTYPES)
            elif a == "--param-dtype":
                cfg.param_dtype = val().lower()
                _validate_dtype_field("param_dtype", cfg.param_dtype,
                                      VALID_PARAM_DTYPES)
            elif a == "--serve-model-name":
                cfg.serve_model_name = val()
            elif a == "--serve-max-queue-rows":
                cfg.serve_max_queue_rows = int(val())
            elif a == "--serve-admission":
                cfg.serve_admission = val().lower()
            elif a == "--serve-starvation-ms":
                cfg.serve_starvation_ms = float(val())
            elif a == "--serve-gen-slots":
                cfg.serve_gen_slots = int(val())
            elif a == "--serve-gen-max-seq":
                cfg.serve_gen_max_seq = int(val())
            elif a == "--serve-gen-max-new":
                cfg.serve_gen_max_new_tokens = int(val())
            elif a == "--serve-kv-page":
                cfg.serve_kv_page = int(val())
            elif a == "--serve-kv-pages":
                cfg.serve_kv_pages = int(val())
            elif a == "--serve-prefix-cache":
                cfg.serve_prefix_cache = val().lower()
                if cfg.serve_prefix_cache not in ("on", "off"):
                    raise ValueError(
                        f"--serve-prefix-cache must be 'on' or 'off', "
                        f"got {cfg.serve_prefix_cache!r}")
            elif a == "--serve-prefill-chunk":
                cfg.serve_prefill_chunk = int(val())
            elif a == "--serve-spec-gamma":
                cfg.serve_spec_gamma = int(val())
            elif a == "--serve-spec-gamma-max":
                cfg.serve_spec_gamma_max = int(val())
            elif a == "--serve-spec-policy":
                cfg.serve_spec_policy = val().lower()
                if cfg.serve_spec_policy not in ("fixed", "adaptive"):
                    raise ValueError(
                        f"--serve-spec-policy must be 'fixed' or "
                        f"'adaptive', got {cfg.serve_spec_policy!r}")
            elif a == "--trace-sample-rate":
                cfg.trace_sample_rate = float(val())
            elif a == "--metrics-port":
                cfg.metrics_port = int(val())
            elif a == "--metrics-host":
                cfg.metrics_host = val()
            # unknown flags pass through (reference forwards Legion flags)
            i += 1
        return cfg
