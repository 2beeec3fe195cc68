"""KV-cache memory accounting — ONE layout/byte source shared by the
runtime and the static tools (ISSUE 11 satellite; ISSUE 15 tentpole).

Since ISSUE 15 the decode state is a **paged block pool**, not a dense
``(slots, max_seq, ...)`` preallocation: each attention op holds a K and
a V pool and a per-slot page table of gather indices maps logical
positions onto pages.  The pools are stored **lane-dense** (ISSUE 25):
``(num_pages, page_size, heads * head_dim)``, one row per token with
its heads side by side — the folded dim sharded over the
tensor-parallel ``c`` mesh axis where ``c`` divides the heads (each
shard then holds ``heads / c`` whole heads, contiguous); pages are
interchangeable, so the page dim is replicated — any slot may hold any
page.  Why folded: on the TPU an array's two minor dims are tiled
``(8, 128)``, and ``(heads, head_dim)`` = 12 x 64 fills no tile, so the
row scatter, the page gather and the attention einsums each wanted a
different layout of the old ``(num_pages, page_size, heads, head_dim)``
pool and XLA converted the WHOLE pool between them — eight pool-sized
transposing copies a layer in decode, six in prefill, 84 % of the
device's time in the serve cell (PERF.md, PR 25).  With
``heads * head_dim`` a multiple of 128 the folded rows are the tiles:
the scatter updates the donated pool in place, the gather reads it,
and only the GATHERED view is unfolded to ``(.., heads, head_dim)``
for the einsums.  The bytes are the same in the same order, so the
accounting below did not change.  HBM
therefore scales with *pages*, and shared-prefix reuse (the prefix trie
in ``serving/generation/pages.py``) makes pages-in-use scale with LIVE
tokens rather than ``slots x max_seq``.  LSTM ops keep their f32
``(h, c)`` state pair of ``(slots, hidden)`` — cell state is positional
carry, not a pageable sequence.

That HBM is resident for the life of the engine — exactly the kind of
allocation a static HBM gate must know about, so :func:`kv_page_plan`
(and its scalar :func:`kv_cache_bytes`) is consumed by

* the :class:`~flexflow_tpu.serving.generation.GenerationEngine`
  (which also derives its actual pool placement from
  :func:`kv_cache_layout` — the runtime allocates what this module
  predicts, byte for byte, ``tests/test_generation.py`` pins it);
* ``flexflow-tpu lint --serve-slots N --serve-seq S`` — the FF108 HBM
  gate and the FF121 liveness timeline both add the same scalar, so
  lint and the engine cannot disagree about whether a generation
  deployment fits;
* ``flexflow-tpu explain`` — the memory report's ``kv_cache`` section
  carries the same plan (pages, page_bytes, pool bytes);
* the fleet co-residency gate (FF130/FF131,
  ``serving/fleet/gate.py``) — generation tenants charge the pool.

The default pool is sized to the dense worst case
(``slots x ceil(max_seq / page_size)`` pages), so with ``page_size``
dividing ``max_seq`` the GLOBAL accounting equals the pre-paging dense
number, while the engine's *in-use* high-water mark
(``stats()["kv_pages_high_water"]``) drops with sharing.  One sharding caveat: the old dense cache
slot-sharded over ``n`` where it divided; the pool's page dim is
replicated (any slot must be able to borrow any page), so on a mesh
where slot-sharding used to engage the PER-DEVICE KV bytes grow by
that factor — re-run lint for n-sharded deployments, the old plan does
NOT carry over there.

Device-free: meshes are plain ``{axis: size}`` dicts (the
:class:`~flexflow_tpu.parallel.mesh.AbstractMesh` view), so a 64-chip
serving deployment is sized from a laptop.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from ..op import Op

# the LSTM decode carry stays f32 across timesteps (ops/rnn.py keeps
# cell state in f32 for stability) regardless of the compute dtype
STATE_DTYPE_BYTES = 4

# tokens per KV page (FFConfig.serve_kv_page's default).  16 keeps page
# internal fragmentation under one short prompt while staying a
# lane-friendly minor-dim multiple for the gathered attention view.
DEFAULT_PAGE_SIZE = 16


def _axis(mesh_sizes: Optional[Dict[str, int]], axis: str) -> int:
    return max(1, int((mesh_sizes or {}).get(axis, 1)))


def slot_shard_degree(slots: int, mesh_sizes: Optional[Dict[str, int]]
                      ) -> int:
    """How many ways the slot (decode-batch) dim shards over the data
    axis ``n`` — mirrors ``FFModel._infer_batch_entries``'s rule: never
    below 2 slots per shard (a 1-row shard lowers to matrix-vector
    kernels and breaks the decode==forward parity contract), replicate
    when the axis does not divide.  Applies to the LSTM state pair (and
    the decode activations); the attention page POOL never slot-shards
    — pages are interchangeable across slots."""
    n = _axis(mesh_sizes, "n")
    if n > 1 and slots % n == 0 and slots >= 2 * n:
        return n
    return 1


def _check_page_args(page_size: int, num_pages: int = 0) -> None:
    """Reject negative page knobs LOUDLY: ``int(x) or default`` keeps
    a negative value, and a negative geometry flowing into the byte
    math yields a negative KV charge — a gate that lint would PASS on
    while the engine (GraphDecoder validates the same knobs) refuses
    to build.  0 stays the default/auto sentinel everywhere."""
    if page_size < 0 or num_pages < 0:
        raise ValueError(
            f"page_size/num_pages must be >= 0 (0 = default/auto), "
            f"got {page_size}/{num_pages}")


def pages_per_slot(max_seq: int, page_size: int = DEFAULT_PAGE_SIZE
                   ) -> int:
    """Page-table width: pages needed to hold one ``max_seq`` stream."""
    _check_page_args(page_size)
    page_size = int(page_size) or DEFAULT_PAGE_SIZE
    return -(-int(max_seq) // page_size)  # ceil


def default_num_pages(slots: int, max_seq: int,
                      page_size: int = DEFAULT_PAGE_SIZE) -> int:
    """The auto pool size (``serve_kv_pages=0``): the dense worst case
    — every slot holding a full private ``max_seq`` stream.  Sharing
    and mixed lengths keep the in-use high-water BELOW this; an
    operator shrinks the pool once the engine's stats show the real
    mark."""
    return int(slots) * pages_per_slot(max_seq, page_size)


def window_rows(window: int, max_seq: int, page_size: int,
                prefill_chunk: int = 0) -> int:
    """Rows a slot holds of a layer that reads the last ``window``
    positions only: the window and the longest prompt chunk written before
    it is read (``prefill_chunk``; 0 = whole prompts), never more than
    ``max_seq``, in whole pages."""
    rows = min(int(window) + (int(prefill_chunk) or int(max_seq)),
               int(max_seq))
    return -(-rows // page_size) * page_size


# the layout entry of what an op with page-major leaves ALSO counts on the
# device (``Op.serve_state``'s ``"counters"``), beside the op's own
COUNTERS = "/counters"


def kv_cache_layout(layers: List[Op],
                    mesh_sizes: Optional[Dict[str, int]],
                    slots: int, max_seq: int,
                    page_size: int = DEFAULT_PAGE_SIZE,
                    num_pages: int = 0,
                    prefill_chunk: int = 0) -> Dict[str, Dict]:
    """Per-op decode-state geometry: ``{op_name: {"kind":
    "kv"|"state", "shapes": {leaf: shape}, "entries": {leaf:
    PartitionSpec entries}, "dtype": "compute"|"f32"}}``, each entry
    what that op DECLARES (``Op.serve_state`` — a layer's serving form
    lives in the layer; an op that keeps nothing has no entry).  A ``"kv"``
    entry's leaves are page-major ``(num_pages, page_size, width)`` with
    whatever ``width`` the op DECLARES: a K and a V leaf of ``kv_heads *
    head_dim`` each (``MultiHeadAttention``), or one leaf of a compressed
    row all heads share, padded to whole lane tiles (``LatentAttention``:
    576 values stored as 640); nothing here derives a width from heads.  An
    entry that declares a ``"window"`` is given ROWS OF ITS OWN here, a
    ring a slot addressed by arithmetic on (slot, position) — leaves
    ``(slots, rows // page_size, page_size, ..)`` with ``"rows"``
    (:func:`window_rows`) noted on the entry — instead of pages of the
    shared pool: the pool's page ids then index the other layers only.
    An op that a looped stack calls ``loop_passes`` times a token
    (``Op.loop_passes``; ``models/decoder_lm.py``) keeps a cache of its own
    for every pass: its page-major leaves get ``loop_passes`` REGIONS of the
    pool's pages (``(loop_passes * num_pages, page_size, ..)``, ``"passes"``
    noted on the entry; pass ``t`` of page ``p`` is row ``t * num_pages +
    p``), so a page id means one page in every pass and a token costs
    ``loop_passes`` rows; the later passes' ops (``Op.loop_source``) declare
    nothing of their own.
    A ``"kv"`` entry that also declares ``"counters"`` (``{"shapes",
    "entries"}``: what the op counts on the device beside its pages) gets
    them as an entry of their own, kind ``"counter"``, named ``<op> +
    COUNTERS``, so that everything said of a ``"kv"`` entry stays true of
    it; :meth:`GraphDecoder._walk` hands the op both.
    The one place the declarations are gathered — the generation decoder
    allocates exactly this (through ``serving/generation/pages.py``, the
    only module allowed to allocate it — repo_lint RL013), and
    :func:`kv_page_plan` integrates exactly this."""
    _check_page_args(page_size, num_pages)
    page_size = int(page_size) or DEFAULT_PAGE_SIZE
    pool = int(num_pages) or default_num_pages(slots, max_seq, page_size)
    out: Dict[str, Dict] = {}
    for op in layers:
        if op.loop_source is not None:
            continue    # a later pass's call: its state is its source's
        entry = op.serve_state(int(slots), pool, page_size, mesh_sizes)
        if entry is None:
            continue
        if op.loop_passes > 1 and entry["kind"] != "counter":
            if entry["kind"] != "kv" or entry.get("window"):
                raise ValueError(
                    f"{op.name} is run {op.loop_passes} times a token and "
                    f"keeps state that is not pages of the shared pool: a "
                    f"pass's state of its own needs page-major leaves")
            # a region of the pool's pages for each pass, in ONE leaf
            entry = dict(entry, passes=op.loop_passes, shapes={
                leaf: (op.loop_passes * shape[0],) + tuple(shape[1:])
                for leaf, shape in entry["shapes"].items()})
        if entry.get("window"):
            rows = window_rows(entry["window"], max_seq, page_size,
                               prefill_chunk)
            entry = dict(
                entry, rows=rows,
                shapes={leaf: (int(slots), rows // page_size) + tuple(
                    shape[1:]) for leaf, shape in entry["shapes"].items()},
                entries={leaf: (None,) + tuple(e) for leaf, e in
                         entry["entries"].items()})
        counters = entry.get("counters")
        if counters:    # an op that pages AND counts: an entry for each
            entry = {k: v for k, v in entry.items() if k != "counters"}
            out[op.name + COUNTERS] = dict(counters, kind="counter",
                                           dtype="i32")
        out[op.name] = entry
    return out


def kv_page_plan(layers: List[Op],
                 mesh_sizes: Optional[Dict[str, int]],
                 slots: int, max_seq: int,
                 kv_dtype_bytes: int = 2,
                 page_size: int = DEFAULT_PAGE_SIZE,
                 num_pages: int = 0,
                 prefill_chunk: int = 0) -> Dict:
    """THE page-pool accounting: per-DEVICE bytes of the paged decode
    state, each kind of entry at what it holds.  Returns ``{"page_size",
    "pages_per_slot", "num_pages", "page_bytes", "pool_bytes",
    "window_bytes", "window_rows", "state_bytes", "total_bytes"}`` where
    ``page_bytes`` is the per-device cost of ONE page summed over the
    leaves, at their declared widths, of every attention op that pages (a K
    and a V pool, or one latent pool; ``kv_dtype_bytes`` each — the compute
    dtype, 2 for bf16, 4 for f32 — a leaf sharded over ``c`` divided by it),
    ``pool_bytes = num_pages * page_bytes``, ``window_bytes`` the rows of
    the WINDOWED entries (``slots x window_rows`` each, whatever the pool:
    ``prefill_chunk`` sizes them, :func:`window_rows`), and
    ``state_bytes`` the f32 LSTM ``(h, c)`` carry (``slots/n x hidden/c``)
    and the ops' counters.  Integrates
    :func:`kv_cache_layout` leaf-for-leaf, so the engine's real
    allocation and these numbers cannot drift apart; the engine's
    high-water mark is ``pages_high_water * page_bytes + state_bytes``
    with the SAME ``page_bytes``."""
    _check_page_args(page_size, num_pages)
    page_size = int(page_size) or DEFAULT_PAGE_SIZE
    pool = int(num_pages) or default_num_pages(slots, max_seq, page_size)
    layout = kv_cache_layout(layers, mesh_sizes, slots, max_seq,
                             page_size=page_size, num_pages=pool,
                             prefill_chunk=prefill_chunk)
    n_deg = slot_shard_degree(slots, mesh_sizes)
    c = _axis(mesh_sizes, "c")
    page_bytes = 0.0
    state_bytes = 0.0
    window_bytes = 0.0
    rows = 0
    for entry in layout.values():
        bytes_per = (kv_dtype_bytes if entry["dtype"] == "compute"
                     else STATE_DTYPE_BYTES)
        for leaf, shape in entry["shapes"].items():
            vol = 1
            for s in shape:
                vol *= int(s)
            parts = 1
            for e in entry["entries"][leaf]:
                if e == "n":
                    parts *= n_deg
                elif e == "c":
                    parts *= c
            if entry["kind"] != "kv":
                state_bytes += vol * bytes_per / parts
            elif entry.get("window"):
                window_bytes += vol * bytes_per / parts
                rows = max(rows, entry["rows"])
            else:
                # per-page cost: the pool volume divided by its pages
                page_bytes += vol * bytes_per / parts / pool
    return {
        "page_size": page_size,
        "pages_per_slot": pages_per_slot(max_seq, page_size),
        "num_pages": pool,
        "page_bytes": page_bytes,
        "pool_bytes": page_bytes * pool,
        "window_bytes": window_bytes,
        "window_rows": rows,
        "state_bytes": state_bytes,
        "total_bytes": page_bytes * pool + window_bytes + state_bytes,
    }


def kv_cache_bytes(layers: List[Op],
                   mesh_sizes: Optional[Dict[str, int]],
                   slots: int, max_seq: int,
                   kv_dtype_bytes: int = 2,
                   page_size: int = DEFAULT_PAGE_SIZE,
                   num_pages: int = 0, prefill_chunk: int = 0) -> float:
    """Per-DEVICE bytes of the preallocated paged decode state — the
    scalar the FF108/FF121/FF130 gates charge (the ``total_bytes`` of
    :func:`kv_page_plan`).  With the default pool size and a
    ``page_size`` dividing ``max_seq`` this equals the pre-paging dense
    number on meshes where the dense cache did not slot-shard; where it
    did (``n`` dividing ``slots``), the replicated page dim makes the
    per-device charge larger by that degree — see the module
    docstring's sharding caveat."""
    return kv_page_plan(layers, mesh_sizes, slots, max_seq,
                        kv_dtype_bytes=kv_dtype_bytes,
                        page_size=page_size, num_pages=num_pages,
                        prefill_chunk=prefill_chunk)["total_bytes"]


def default_serve_seq(input_tensors) -> Optional[int]:
    """The ``--serve-seq`` default: the model's sequence length when it
    has a sequence-shaped input, else None (the caller must require an
    explicit flag).  ONE implementation shared by ``lint`` and
    ``explain`` so the two subcommands can never default the same
    model to different KV sizes."""
    tins = list(input_tensors or [])
    if tins and len(tins[0].shape) > 1:
        return int(tins[0].shape[1])
    return None


def dtype_bytes(dtype_name: str) -> int:
    """Byte width of a compute dtype name ('bfloat16' -> 2,
    'float32' -> 4) — shared by the engine and the CLI so both feed
    :func:`kv_page_plan` the same ``kv_dtype_bytes``."""
    import numpy as np
    try:
        return int(np.dtype(dtype_name).itemsize)
    except TypeError:
        # np has no bfloat16; it is 2 bytes
        return 2 if "bfloat16" in str(dtype_name) else 4


__all__ = ["kv_cache_layout", "kv_cache_bytes", "kv_page_plan", "window_rows",
           "slot_shard_degree", "pages_per_slot", "default_num_pages",
           "dtype_bytes", "default_serve_seq", "STATE_DTYPE_BYTES",
           "DEFAULT_PAGE_SIZE"]
