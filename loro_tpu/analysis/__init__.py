"""Project-invariant static analysis + runtime lock-order witness.

The repo's survival rules used to live only as prose (never signal
the chip's owner, the pad-bucket jit-cache invariant, "every device call
routes through DeviceSupervisor", keyed-blake2b-never-``hash()``
placement, the typed-error discipline).  This package turns them into
CI failures instead of post-mortems:

- **tpulint** (``python -m loro_tpu.analysis.lint loro_tpu chip_smoke.py``):
  an AST-based rule registry (``rules.py``) with per-line
  ``# tpulint: disable=RULE(reason)`` pragmas and a checked-in
  baseline; the tier-1 gate in tests/test_analysis.py fails on any
  unsuppressed finding, so every future PR inherits the discipline.
- **lock witness** (``lockwitness.py``): the named-lock wrapper the
  threaded fleet planes (PipelinedIngest, ShardedResidentServer,
  FanIn, SyncServer, DeviceSupervisor, the batch device locks) build
  their locks through.  Enabled under tests it records the runtime
  lock-acquisition graph, asserts it acyclic and conformant to the
  declared partial order in ``lockorder.py``, and dumps the witnessed
  graph as an artifact.

Everything here is pure stdlib (no jax import) so the linter runs in
milliseconds anywhere, including pre-commit hooks.
"""
# lazy exports: `python -m loro_tpu.analysis.lint` must not import the
# submodule at package-import time (runpy double-import warning), and
# lock adopters importing lockwitness must not pull the lint engine in
_EXPORTS = {
    "Finding": "core", "LintResult": "core", "Rule": "core",
    "all_rules": "core", "get_rule": "core",
    "lint_paths": "lint", "lint_source": "lint",
    "LockWitness": "lockwitness", "named_lock": "lockwitness",
    "named_rlock": "lockwitness", "witness": "lockwitness",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(f".{mod}", __name__), name)
