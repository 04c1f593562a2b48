"""graftlint: project-invariant static analysis (stdlib ``ast`` only).

Every rule here encodes an invariant this repo has already paid for in a
review-hardening pass — uncached jit wrappers re-traced on the save path
(PR 7), shared mutable state written outside its lock in the threaded
serve layer (PRs 4/5/6/8), silently-swallowed exceptions, and drift
between code and its contracts (env knobs vs config/docs, health-event
kinds vs docs/TELEMETRY.md).  docs/ANALYSIS.md is the rule catalog;
``tools/graftlint.py`` is the CLI; ``tests/test_lint.py`` is the tier-1
gate (zero unsuppressed findings over hydragnn_tpu/, tools/, tests/).

IMPORTANT: this package must stay importable WITHOUT jax/flax/numpy —
the CLI loads it standalone (importlib spec, bypassing the heavyweight
``hydragnn_tpu.__init__``) so a lint pass costs milliseconds, not a jax
import.  Use only stdlib modules and RELATIVE imports here.
"""

from .core import (  # noqa: F401
    Finding,
    Rule,
    Severity,
    all_rules,
    get_rule,
    register,
)
from .project import FileCtx, Project, collect_project  # noqa: F401
from .runner import (  # noqa: F401
    LintResult,
    load_baseline,
    run_project,
    write_baseline,
)
from .registry import (  # noqa: F401
    HEALTH_KINDS,
    KNOBS,
    TRACE_DOC_BEGIN,
    TRACE_DOC_END,
    emit_knob_docs,
    emit_trace_docs,
)

# importing the rule modules registers every rule
from .rules import lock_coverage  # noqa: F401
from .rules import registries  # noqa: F401
from .rules import robustness  # noqa: F401
from .rules import trace_hygiene  # noqa: F401
