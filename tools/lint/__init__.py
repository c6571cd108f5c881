"""paddle_tpu-lint — invariant-aware static analysis for this repo.

Ten PRs of serving work rest on invariants that were previously enforced
only by reviewer vigilance and after-the-fact regression tests. This
package encodes them as AST checkers that fail CI at the violating line:

========  ==================================================================
checker   invariant (and the PR that established it)
========  ==================================================================
PT001     recompile hazard: a ``jax.jit``/``monitored_jit`` callable
          constructed per call (inside a method/loop body), or a
          Python-varying value traced without ``static_argnames`` —
          the ONE-compiled-program bar (PR 2/3/10).
PT002     host sync in a hot path: ``.item()`` / ``np.asarray`` /
          ``jax.device_get`` / ``block_until_ready`` / device-scalar
          coercion reached from a ``# lint: hot-path`` function —
          the never-block-the-gap / lock-light ``load()`` bar (PR 9).
PT003     series lifecycle: a monitor Counter/Gauge/Histogram created
          with an instance label (server/engine/pool/router/loader/fit)
          must be retired in the owning class's close/shutdown —
          the leak class PR 8's retirement test caught at runtime.
PT004     lock discipline: fields declared ``# guarded-by: self._lock``
          accessed outside a ``with self._lock`` block (PR 4/9's
          threaded serving classes).
PT005     flag gating: monitor/trace recording work not branching on its
          enable flag first — the near-zero-when-off bar (PR 1/8).
PT006     blocking socket I/O in a hot path: ``urlopen`` / connection
          constructors without a bounded ``timeout=``, or raw
          ``.recv``/``.accept``/``.getresponse`` reads reached from a
          ``# lint: hot-path`` function — the cached-snapshot-only bar
          the cross-process fleet's routing seam rides on (PR 17).
PT007     eager device computation in a hot path: ``jax.random.*``,
          ``x.at[...]`` or a ``jnp.*`` call other than ``jnp.asarray``
          reached from a ``# lint: hot-path`` function and outside a
          jitted def — each is a compiled program of its own between
          two segments; the one-dispatch-a-segment bar (PR 31).
========  ==================================================================

Run ``python -m tools.lint paddle_tpu/``; see ``tools/lint/baseline.json``
for the triaged pre-existing findings (the bar is "no NEW violations").
The annotation grammar (``# lint: ...`` / ``# guarded-by: ...``) is
documented in MIGRATING.md under "Static analysis annotations".
"""
from .core import (BaselineError, Finding, Module, apply_baseline,
                   default_baseline_path, fingerprint_findings,
                   generate_baseline, lint_paths, lint_source,
                   load_baseline, write_baseline)
from .checks import CHECKERS

__all__ = [
    "BaselineError", "Finding", "Module", "CHECKERS",
    "lint_paths", "lint_source",
    "load_baseline", "write_baseline", "apply_baseline",
    "generate_baseline", "fingerprint_findings", "default_baseline_path",
]
