"""repro.analysis — static race detection, schedule verification, and
sanitizer support for the LPF program IR.

The paper's model-compliance stance is that every primitive has strict,
checkable semantics.  The numpy differential oracle and the ledger tests
enforce those semantics *dynamically*, after execution; this package
proves the optimizer's legality invariants *statically*, on the IR:

* :mod:`repro.analysis.linter` — race/hazard lint over recorded traces
  with stable diagnostic codes LPF001–LPF006;
* :mod:`repro.analysis.verifier` — an independent re-derivation of the
  must-precede conflict DAG that certifies an optimized schedule
  (topological order, commuting merges, overlap contracts, Valiant
  rewrites on conflict-free tables, cost compliance) — the certificate
  :meth:`repro.core.ProgramCache.certify` attaches to every cache entry
  and every recorded program needs before it executes;
* :mod:`repro.analysis.traces` — the canned benchmark traces, shared
  with ``benchmarks/schedule_search.py``;
* ``python -m repro.analysis`` — the CLI (see ``__main__.py``).

Sanitizer mode (``LPF_SANITIZE=1`` or ``LPFContext(sanitize=True)``)
runs the linter on every recorded trace at flush time: error
diagnostics raise :class:`repro.core.LPFAnalysisError`, warnings
accumulate on ``ctx.diagnostics``.
"""

from .linter import Diagnostic, ERROR, WARNING, lint_program, lint_trace
from .verifier import VerifierReport, verify_program
from .traces import (CANNED_TRACES, canned_bucketed_trace,
                     canned_fft_trace, canned_fragmented_trace,
                     canned_pagerank_trace)

__all__ = [
    "Diagnostic", "ERROR", "WARNING", "lint_trace", "lint_program",
    "VerifierReport", "verify_program",
    "CANNED_TRACES", "canned_fft_trace", "canned_bucketed_trace",
    "canned_fragmented_trace", "canned_pagerank_trace",
]
