"""raftlint: JAX + concurrency static analysis and contracts for raft-tpu.

Four halves:

* :mod:`raft_tpu.lint.engine` + :mod:`raft_tpu.lint.rules` — an AST
  analysis suite (no jax import, scanned code is never executed) catching
  the silent JAX failure modes that burn TPU hours: side effects and host
  syncs under trace (R1/R6), recompilation storms (R2), PRNG misuse (R3),
  float64 creep (R4), where-NaN gradient traps (R5), donated-buffer reuse
  (R7), missing flow-iterate detach (R8), contract drift (R9), bare
  library prints (R10) — plus the lock-discipline family C1-C6 for the
  threaded serving plane (unguarded shared writes, blocking under a lock,
  lock-order cycles/inversions, wait predicates, check-then-act inits,
  unsynchronized counters) and the serving budget family B1-B4
  (request-derived shapes into jit, unwarmed engine-cache kinds, hot-path
  device allocation, hardcoded VMEM/HBM constants).
* :mod:`raft_tpu.lint.budget` — the static capacity analyzer behind
  ``raftlint --budget``: it reads the warmup grid the engine's warmup
  iterates (``serving/config.py``) and the block plans the Pallas kernels
  execute (``raft_tpu/kernel_plans.py``), and prices them: ``jax.eval_shape``
  HBM footprints and VMEM envelopes.
* :mod:`raft_tpu.lint.concurrency` — the ``guarded_by`` annotation layer
  and the shared class/lock analysis the C rules, the SERVING.md
  threading-model generated check, and the runtime lock-order validator
  (telemetry/watchdogs.py) all agree on.
* :mod:`raft_tpu.lint.contracts` — ``@contract`` shape/dtype specs on the
  hot-path signatures, checked statically by R9 and (opt-in) at trace time.

CLI: ``python tools/raftlint.py [paths] [--strict]`` and
``python tools/raftlint.py --budget [--strict]``.  Docs: LINT.md.
"""

from .concurrency import SERVING_LOCK_HIERARCHY, guarded_by  # noqa: F401
from .contracts import (ContractError, checking_enabled, contract,  # noqa: F401
                        enable_checking, parse_spec)
from .engine import (Finding, Rule, RULES, register, scan_paths,  # noqa: F401
                     scan_source)
