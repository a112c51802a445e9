"""tpulint — JAX/TPU-aware static analysis for this tree, whole-program.

Three rule families, all distilled from bugs this repo actually shipped
or could only catch probabilistically at runtime:

- ``TPU1xx`` (rules_jax, rules_sharding): closure-captured arrays in
  jitted programs, host syncs inside traced functions, import-time
  device work, missing buffer donation on train steps, and mesh-axis
  drift in ``in_shardings``/``NamedSharding`` specs.
- ``LOCK2xx`` (rules_lockset, rules_order): an Eraser-style lockset
  checker for the hand-rolled mutex idiom of the control plane (now
  propagating lock context across modules through the call graph in
  ``callgraph.py``), lock-order-cycle (ABBA deadlock) detection,
  check-then-act atomicity, and blocking-call detection in reconciles.
- ``HYG00x`` (hygiene + core): parse/debugger/conflict gates and the
  stale-suppression audit (HYG004).

``dyntrace.py`` is the dynamic half: an opt-in happens-before tracer
that instruments control-plane classes during the race tier and diffs
observed locksets against LOCK201's static guarded-attribute map.

CLI: ``python -m kubeflow_tpu.analysis [paths...]`` — exits nonzero on
findings; ``--format sarif`` for CI uploads, ``--baseline``/
``--write-baseline`` for the ratchet. Suppress a finding in-line with
``# tpulint: disable=RULE  <justification>``. docs/static-analysis.md
documents every rule.
"""

from kubeflow_tpu.analysis.core import (  # noqa: F401
    Finding, Module, ProgramRule, Rule, all_rules, register, scan_paths,
    scan_source, scan_sources,
)
from kubeflow_tpu.analysis.report import (  # noqa: F401
    render_json, render_sarif, render_text,
)

__all__ = ["Finding", "Module", "ProgramRule", "Rule", "all_rules",
           "register", "scan_paths", "scan_source", "scan_sources",
           "render_json", "render_sarif", "render_text"]
