"""ffcheck — static strategy & graph verification with structured
diagnostics.

One legality story for the whole stack (ISSUE 3): the MCMC search, the
trace-time sharding fallbacks and this verifier all judge a
``ParallelConfig`` through :mod:`analysis.legality`, so the simulator can
never cost a split the executor silently replicates.  Entry points:

* :func:`verify` — static, device-free graph + strategy verification;
* :func:`verify_compile` — the ``FFModel.compile(verify=...)`` hook;
* ``flexflow-tpu lint`` (cli.py) — builtin model + strategy ``.pb`` to
  diagnostics, nonzero exit on ERROR;
* the diagnostic-code table lives in ``docs/verifier.md``.
"""

from .diagnostics import (CODES, Diagnostic, DiagnosticReport, Severity,
                          VerificationError, make, validate_report_json)
from .kv_memory import kv_cache_bytes, kv_cache_layout
from .legality import config_diagnostics, degree_executable, per_dim_degrees
from .sharding_passes import (comm_plan_digest, communication_plan,
                              explain_report, predict_fallbacks,
                              propagate_specs, render_explain_text,
                              validate_explain_json)
from .verifier import (drain_fallback_sites, drain_replicate_fallbacks,
                       record_replicate_fallback, verify, verify_compile)

__all__ = [
    "CODES", "Diagnostic", "DiagnosticReport", "Severity",
    "VerificationError", "make", "config_diagnostics", "degree_executable",
    "per_dim_degrees", "verify", "verify_compile",
    "record_replicate_fallback", "drain_replicate_fallbacks",
    "drain_fallback_sites", "predict_fallbacks", "propagate_specs",
    "communication_plan", "comm_plan_digest", "explain_report", "render_explain_text", "validate_explain_json",
    "validate_report_json", "kv_cache_bytes", "kv_cache_layout",
]
