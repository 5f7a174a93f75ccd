"""repro.analysis: static invariant checks over jaxprs and compiled HLO.

The graph auditor behind ``repro.launch.forecast analyze`` and the CI
zero-violation gate. Five lints prove the repo's load-bearing performance
claims per commit instead of observing them:

* :mod:`repro.analysis.recompile` -- bounded-jit-cache sentinel (true XLA
  compile counts vs a declared budget),
* :mod:`repro.analysis.gradleak` -- frozen param groups build no gradients,
* :mod:`repro.analysis.donation` -- donated buffers actually alias,
* :mod:`repro.analysis.collectives` -- sharded predict is collective-free;
  the sharded loss grad psums and does nothing else,
* :mod:`repro.analysis.dtypes` -- no f64 promotion / above-policy upcasts.

:mod:`repro.analysis.hlo_text` is the shared HLO text parsing layer (also
consumed by the roofline extractors); :mod:`repro.analysis.audit` wires the
lints to the real fit/predict/serve entry points and emits the JSON report.

:mod:`repro.analysis.spans` is the program's span facility: named phases of
the trainer loop, the predict entry and the forecast server, written to the
profiler's trace and kept in memory while a trace is active.
"""

from repro.analysis.audit import (           # noqa: F401
    AuditReport, AuditSection, audit_collectives, audit_fit, audit_predict,
    audit_serve, run_audit,
)
from repro.analysis import spans             # noqa: F401
from repro.analysis.gradleak import Finding  # noqa: F401
from repro.analysis.recompile import (       # noqa: F401
    CompileBudgetExceeded, CompileCounter, check_compile_budget,
)

__all__ = [
    "AuditReport", "AuditSection", "Finding",
    "CompileBudgetExceeded", "CompileCounter", "check_compile_budget",
    "audit_collectives", "audit_fit", "audit_predict", "audit_serve",
    "run_audit", "spans",
]
