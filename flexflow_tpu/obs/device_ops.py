"""Device time of a fused program, read by graph op.

XLA fuses a whole step into one program, but every instruction of the
compiled module still carries the name stack it was traced under in its
``metadata={op_name="..."}``, and ``FFModel`` runs each graph op, the loss,
the metrics and the optimizer update under a ``jax.named_scope`` of its own.
So a profiler trace of the REAL step can be summed by graph op, forward and
backward apart — where ``flexflow_tpu/profiling.py`` times each op compiled
in isolation.  The serving programs (``GraphDecoder``) run each op's
``serve_step`` under the same scope and what they do outside any graph op
under :data:`SERVE_OWNERS`; an op that opens scopes of its own inside
(``Op.scopes``: the MoE's router, experts and shared expert) is told apart
by PART where the train step is told apart by pass.

:func:`table_from_hlo` maps every instruction of a compiled module's text to
its owner; :func:`attribute` sums a trace's ``[name, start, duration]``
operations by that table.  What it cannot see (looked at on a v5e, PR 24): a
fusion carries ONE instruction's metadata, so where XLA fuses the tail of one
graph op into the head of the next the whole fusion goes to one of them; and
an instruction XLA made itself from a parameter (a layout copy of a weight,
a buffer's initial broadcast) names that parameter, not a scope, and is
owned by nobody.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, Optional, Sequence, Tuple

# the scopes FFModel's train step opens beside one per graph op
STEP_OWNERS = ("loss", "metrics", "optimizer")
# the scopes the serving programs open beside one per graph op: the argmax
# or the sampler with the indexing of the row it reads (``sample``), the
# token splice and a draft program's transposes (``step_io``), a verify
# program's window and accept logic (``speculate``)
SERVE_OWNERS = ("sample", "step_io", "speculate")

Owner = Tuple[Optional[str], Optional[str]]

# `%name = type opcode(...), ..., metadata={... op_name="a/b/c" ...}`, with
# or without the leading ROOT; the first path of a `;`-joined op_name
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%(?P<name>[^\s=]+)\s*=.*?'
    r'metadata=\{[^}]*?op_name="(?P<path>[^";]*)', re.M)
# a transform's frame round a scope: `jvp(x)`, `transpose(jvp(x))`
_FRAME = re.compile(r"^\w+\((.*)\)$")
# `%name = f32[32,12,512,128]{layout} broadcast(`: dtype, dims and opcode
_RESULT = re.compile(
    r'^\s*(?:ROOT\s+)?%(?P<name>[^\s=]+)\s*=\s*(?P<dtype>\w+)'
    r'\[(?P<dims>[\d,]*)\]\S*\s+(?P<opcode>[\w-]+)\(.*?'
    r'metadata=\{[^}]*?op_name="(?P<path>[^";]*)', re.M)


def _owner(path: str, owners, parts=frozenset()) -> Owner:
    """The first component of the name stack ``path`` that is one of
    ``owners`` once its transform frames are taken off, and beside it the
    innermost component below it that is one of ``parts`` or, where there
    is none, whether the stack lies in the backward pass (``transpose(``
    round a frame; the primitive called ``transpose`` has no parenthesis)
    or the forward pass of a differentiated function (``jvp(``)."""
    stack = path.split("/")
    for depth, part in enumerate(stack):
        while part not in owners:
            m = _FRAME.match(part)
            if m is None:
                break
            part = m.group(1)
        else:
            inner = next((p for p in reversed(stack[depth + 1:])
                          if p in parts), None)
            return part, inner or ("bwd" if "transpose(" in path
                                   else "fwd" if "jvp(" in path else None)
    return None, None


def table_from_hlo(text: str, owners: Iterable[str],
                   parts: Iterable[str] = ()) -> Dict[str, Owner]:
    """``{instruction name: (owner | None, part | "fwd" | "bwd" | None)}``
    for every instruction of the compiled module ``text``
    (``compiled.as_text()``) that carries an ``op_name``.  The names are the
    ones the profiler's ``XLA Ops`` line prints (``fusion.12``); a Pallas
    kernel's custom call is named by XLA after the kernel
    (``flash_mha_bwd_dkv_...512.3``) and is found the same way.  ``owners``
    are the scope names to look for: the graph ops' and :data:`STEP_OWNERS`
    or :data:`SERVE_OWNERS`; ``parts`` the scopes the ops open inside their
    own (``Op.scopes``).  Nothing is guessed: an instruction whose name
    stack holds none of them is owned by ``None``."""
    owners, parts = frozenset(owners), frozenset(parts)
    return {m.group("name"): _owner(m.group("path"), owners, parts)
            for m in _INSTRUCTION.finditer(text)}


_LOCATION = re.compile(r'loc\("([^"]+)"')


def unnamed_owners(lowered, table: Dict[str, Owner],
                   owners: Iterable[str]) -> list:
    """Those of ``owners`` under which ``lowered`` (a ``jax.stages.Lowered``)
    traced operations and to which ``table``, read from its compiled text,
    gives no instruction.  jax leaves metadata out of the compilation
    cache's key, so a cache may answer a lowering with an executable
    compiled from a tree whose scopes were others (none at all, an op since
    renamed, a scope since added), and the table read from it is then
    wrong for this tree's program: every owner the lowered text names has
    to turn up in the compiled one.  ``[]`` for an executable compiled
    from this lowering."""
    owners = frozenset(owners)
    traced = {_owner(path, owners)[0]
              for path in _LOCATION.findall(lowered.as_text(debug_info=True))}
    return sorted(traced - {None} - {owner for owner, _ in table.values()})


def stale_cache_error(unnamed: Dict[str, list]) -> RuntimeError:
    """The error for programs whose tables leave owners out
    (``{program: unnamed_owners(...)}``): never a guess."""
    return RuntimeError(
        "the compiled text of " + "; ".join(
            f"{name} gives no instruction to {', '.join(who)}"
            for name, who in sorted(unnamed.items()))
        + ", which the lowered text traces: it was loaded from a "
        "compilation cache written by a tree whose serving programs had "
        "other scopes or none: clear the cache directory (its entries "
        "are named after the program: <name>-<key>)")


def attribute(ops: Sequence, table: Dict[str, Owner]) -> Dict[Owner, float]:
    """Seconds of the traced operations ``[name, start_ns, duration_ns]``
    summed by owner; an operation the table lacks is owned by
    ``(None, None)``."""
    out: Dict[Owner, float] = {}
    for name, _, dur in ops:
        key = table.get(name, (None, None))
        out[key] = out.get(key, 0.0) + dur / 1e9
    return out


def attention_wrapper_ops(text: str, owners: Iterable[str]) -> list:
    """The instructions of a compiled step's ``text`` that only prepare
    operands for a flash-attention kernel, under one of the scopes
    ``owners`` (the attention ops' names): a ``broadcast`` to a rank-4 f32
    array at least 128 wide (the library kernel's backward widens its row
    statistics ``l``, ``m``, ``di`` to ``(n, h, s, 128)`` and ``di`` to
    ``(n, h, s, s)`` in HBM) and a ``copy`` of a rank-4 array (the
    ``(n,s,h,d) <-> (n,h,s,d)`` layout changes round that kernel).  The
    repo's own kernel (``ops/flash_kernel.py``) needs neither, so a step
    that runs it reads ``[]``; names as the profiler prints them."""
    owners = frozenset(owners)
    out = []
    for m in _RESULT.finditer(text):
        dims = [int(d) for d in m.group("dims").split(",") if d]
        if len(dims) != 4 or _owner(m.group("path"), owners)[0] is None:
            continue
        if m.group("opcode") == "copy" or (
                m.group("opcode") == "broadcast"
                and m.group("dtype") == "f32" and dims[-1] >= 128):
            out.append(m.group("name"))
    return out
