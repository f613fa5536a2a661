"""``mx.analysis`` — mxlint, the framework-invariant static analyzer.

Two levels, one idea: the conventions the fault runtime and the perf
work rest on are *checkable artifacts*, not prose.

- :mod:`.lint` — level 1: AST rules (R1–R6) over the repo's own source;
  no project imports executed.  ``tools/mxlint.py`` is the CLI,
  ``tools/run_lint.sh`` the gate.
- :mod:`.hlo` — level 2: named checks on lowered/compiled program text
  (the symbolic half of the mixed imperative/symbolic design), consumed
  by ``tests/test_hlo_perf.py`` and ``mxlint --hlo``.
- :mod:`.modelcheck` — level 3: mxverify, the exhaustive-interleaving
  protocol checker.  It runs the REAL coordination code
  (``fault_dist.coordinated_call``, ``fault_elastic.vote_resize``)
  under a deterministic cooperative scheduler, so unlike its siblings
  it imports the fault runtime — which is why it is lazy here:
  ``tools/mxlint.py`` still loads lint/hlo standalone by file path
  without touching the framework.  ``tools/mxverify.py`` is its CLI.
- :mod:`.race` — level 4 static half: mxrace, the lockset race
  analyzer for the host control plane (thread roots, interprocedural
  locksets, R9/R10), whole-program over the scanned tree but still
  stdlib-only and standalone-loadable.  ``tools/mxrace.py`` is the
  CLI, ``tools/mxrace_baseline.txt`` the ratchet.
- :mod:`.racecheck` — level 4 dynamic half: vector-clock
  happens-before confirmation of race findings over real threads,
  with drop-lock mutation seams proving the checker alive (lazy like
  modelcheck: its scenarios load the code they drive on demand).

lint, hlo, and race are stdlib-only so the CLIs can load them
standalone, without importing (and jax-initializing) the mxnet_tpu
package.
"""
from . import hlo, lint, race  # noqa: F401


def __getattr__(name):
    if name in ("modelcheck", "racecheck"):
        import importlib
        mod = importlib.import_module("." + name, __name__)
        globals()[name] = mod
        return mod
    raise AttributeError("module %r has no attribute %r"
                         % (__name__, name))
from .hlo import HloCheckResult, run_text_checks  # noqa: F401
from .lint import (  # noqa: F401
    Diagnostic, Rule, RULES, apply_baseline, lint_paths, lint_source,
    load_baseline, rule,
)
