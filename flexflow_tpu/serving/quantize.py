"""Int8 weight-only quantization for the serving bucket executables
(ISSUE 14; docs/serving.md "Int8 weight quantization").

Scheme: per-OUTPUT-channel symmetric quantization of the eligible
matmul kernels — for a ``(out, in)`` Linear kernel ``w``, each output
row ``c`` gets ``scale[c] = max|w[c, :]| / 127`` and
``q[c, :] = rint(w[c, :] / scale[c])`` in int8.  Because the scale is
per output channel, ``x @ (q * scale).T == (x @ q.T) * scale`` holds
EXACTLY, so the dequantization fuses into the matmul's epilogue
(``ops.common.dequant_matmul``) and the f32 weight never materializes:
the resident buffer is the int8 tensor plus a tiny f32 ``(out,)`` scale
vector — ~1/4 the HBM footprint and weight-streaming bandwidth of f32.

Quality bound: symmetric round-to-nearest guarantees
``|w - q * scale| <= scale / 2`` per channel, so the model-wide
``max_abs_err`` can never exceed ``max(scale) / 2``.  The measured
error and the bound are both in the report; the serving engine checks
``bound_ok`` at warmup and refuses to serve a violating table (the
check firing means the quantizer itself is broken — it is a tripwire,
not a tuning knob).

Eligibility (:func:`eligible_weights`) is THE one predicate, shared by
``FFModel.quantize_weights`` (the runtime) and the fleet co-residency
gate (``serving/fleet/gate.py`` — ``resident_bytes`` must predict the
engine's real allocation byte-for-byte): 2-D ``Linear`` kernels on the
device path.  Biases, norm scales, embeddings and conv filters stay in
their original dtype — kernels dominate serving residency, and the
per-output-channel scheme is exact only for the matmul contraction.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from ..ops.common import scale_param_name as scale_name

INT8_QMAX = 127

QUANT_MODES = ("", "int8")


def eligible_weights(layers) -> List[Tuple[Any, Any]]:
    """``[(op, weight), ...]`` of the kernels int8 quantization applies
    to: 2-D Linear matmul kernels, each ONCE, at its first owner (a
    kernel several ops read, ``FFModel.share_weights``, is one array to
    quantize and one to charge).  Device-free (type/shape checks
    only), so the fleet gate sizes an uncompiled graph with the exact
    predicate the runtime quantizes by."""
    from ..ops.linear import Linear, host_placed
    out = []
    for op in layers:
        if not isinstance(op, Linear):
            continue
        if host_placed(getattr(op, "parallel_config", None)):
            # host-placed params keep the host-gather path; quantizing
            # them would change that contract for negligible HBM win
            continue
        w = getattr(op, "w_kernel", None)
        if w is not None and len(w.shape) == 2 and w in op.own_weights():
            out.append((op, w))
    return out


def eligible_weight_names(layers) -> frozenset:
    return frozenset(w.name for _, w in eligible_weights(layers))


def quantize_array(host: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                              float, float]:
    """Quantize one ``(out, in)`` f32 kernel: returns ``(q int8,
    scale f32 (out,), max_abs_err, error_bound)``.  Pure numpy — the
    same function the tests drive directly to pin the bound."""
    host = np.asarray(host, np.float32)
    amax = np.max(np.abs(host), axis=1) if host.size else np.zeros(
        host.shape[0], np.float32)
    # a zero row quantizes to zeros exactly; tiny floor avoids div-by-0
    scale = np.maximum(amax / INT8_QMAX,
                       np.finfo(np.float32).tiny).astype(np.float32)
    q = np.clip(np.rint(host / scale[:, None]),
                -INT8_QMAX, INT8_QMAX).astype(np.int8)
    if host.size:
        err = float(np.max(np.abs(host - q.astype(np.float32)
                                  * scale[:, None])))
        bound = float(np.max(scale)) * 0.5
    else:
        err = bound = 0.0
    # one-ulp headroom: the bound derivation is exact in real
    # arithmetic; float rounding of (q * scale) may add an ulp
    bound *= 1.0 + 1e-6
    return q, scale, err, bound


def quantize_params(model, mode: str = "int8"
                    ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Quantized copy of ``model._params`` plus the quality report
    (``FFModel.quantize_weights`` is the caller — see its docstring for
    the placement/caching contract).  Eligible kernels are replaced by
    int8 arrays under the weight's existing sharding; their f32 scales
    ride replicated under ``scale_name(w)``."""
    import jax

    if mode != "int8":
        raise ValueError(f"unknown quantization mode {mode!r} "
                         f"(have {', '.join(m for m in QUANT_MODES if m)})")
    new_params: Dict[str, Any] = dict(model._params)
    rows: List[Dict] = []
    max_err = 0.0
    bound = 0.0
    bytes_before = bytes_after = 0
    repl_sharding = None
    if model.mesh is not None and model.mesh.is_distributed:
        import jax.sharding as jsh
        repl_sharding = model.mesh.sharding(jsh.PartitionSpec())
    for op, w in eligible_weights(model.layers):
        arr = model._params.get(w.name)
        if arr is None:
            continue
        host = np.asarray(jax.device_get(arr), np.float32)
        q, scale, err, wbound = quantize_array(host)
        sharding = getattr(arr, "sharding", None)
        q_arr = (jax.device_put(q, sharding) if sharding is not None
                 else jax.device_put(q))
        s_sh = repl_sharding if repl_sharding is not None else sharding
        s_arr = (jax.device_put(scale, s_sh) if s_sh is not None
                 else jax.device_put(scale))
        new_params[w.name] = q_arr
        new_params[scale_name(w.name)] = s_arr
        max_err = max(max_err, err)
        bound = max(bound, wbound)
        bytes_before += int(arr.nbytes)
        bytes_after += int(q.nbytes + scale.nbytes)
        rows.append({"op": op.name, "weight": w.name,
                     "shape": list(w.shape),
                     "scale_max": float(np.max(scale)) if scale.size
                     else 0.0,
                     "max_abs_err": err, "error_bound": wbound})
    report = {
        "mode": mode,
        "weights": rows,
        "max_abs_err": max_err,
        "error_bound": bound,
        "bound_ok": max_err <= bound or not rows,
        "bytes_before": bytes_before,
        "bytes_after": bytes_after,
    }
    return new_params, report


def quantized_params_bytes_delta(layers, strategies, mesh) -> float:
    """Per-device byte DELTA the int8 path applies on top of the f32
    ``static_params_bytes`` accounting (fleet gate): for every eligible
    kernel, the f32 shard (4 B/elem over its placement parts) is
    replaced by the int8 shard (1 B/elem, same parts) plus the
    REPLICATED f32 scale (out x 4 B on every device) — exactly what
    ``quantize_params`` places, so gate == engine byte-for-byte."""
    from ..parallel.sharding import param_spec
    from .fleet.gate import _subaxis_sizes
    sizes = _subaxis_sizes(mesh)
    delta = 0.0
    for op, w in eligible_weights(layers):
        pc = (strategies or {}).get(op.name)
        spec = param_spec(w, pc, mesh, on_fallback=lambda *a: None)
        parts = 1
        for entry in spec:
            if entry is None:
                continue
            names = (entry,) if isinstance(entry, str) else tuple(entry)
            for nm in names:
                parts *= sizes.get(nm, 1)
        vol = 1
        for s in w.shape:
            vol *= int(s)
        delta -= vol * 4.0 / parts          # the f32 shard leaves...
        delta += vol * 1.0 / parts          # ...the int8 shard arrives
        delta += int(w.shape[0]) * 4.0      # replicated (out,) scale
    return delta


__all__ = ["eligible_weights", "eligible_weight_names", "quantize_array",
           "quantize_params", "quantized_params_bytes_delta",
           "scale_name", "INT8_QMAX", "QUANT_MODES"]
