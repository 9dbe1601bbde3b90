"""GL005 — determinism.

The framework's reproducibility contract (bit-exact checkpoint resume,
deterministic fault injection, stable bench numbers) dies quietly when
randomness or wall-clock sneaks into compute paths:

  - the legacy ``np.random.*`` module-level API draws from hidden
    global state — two fits in one process interleave differently than
    two processes, and a library that touches the global seed breaks
    every caller;
  - ``np.random.default_rng()`` / ``random.Random()`` with no seed is
    fresh entropy per call — nothing downstream can be replayed;
  - wall-clock (``time.time``, ``datetime.now``) inside kernel/trainer
    code (``models/``, ``parallel/``, ``native/``, ``ops/``) makes
    numerical results or cache keys time-dependent. Host-side timing
    (``core/timer.py``, retries, serving) is out of scope.
"""

from __future__ import annotations

import ast
from typing import List, Optional

from tools.graftlint.core import Checker, Finding, ParsedFile, Project

_NP_LEGACY = {
    "seed", "rand", "randn", "randint", "random", "random_sample",
    "normal", "uniform", "choice", "shuffle", "permutation", "beta",
    "binomial", "poisson", "exponential", "gamma", "standard_normal",
    "bytes", "sample", "ranf",
}
_STDLIB_RANDOM = {
    "random", "randint", "uniform", "choice", "choices", "shuffle",
    "gauss", "randrange", "sample", "betavariate", "expovariate",
    "normalvariate", "seed", "randbytes", "getrandbits",
}
_WALLCLOCK = {"time.time", "time.time_ns"}
_WALLCLOCK_SUFFIXES = ("datetime.now", "datetime.utcnow", "date.today")
_KERNEL_DIRS = {"models", "parallel", "native", "ops"}


class DeterminismChecker(Checker):
    rule = "GL005"
    name = "determinism"
    description = ("no unseeded/global RNG; no wall-clock in "
                   "kernel/trainer code")

    def check_file(self, pf: ParsedFile,
                   project: Project) -> List[Finding]:
        out: List[Finding] = []
        in_kernel_code = bool(set(pf.rel.split("/")) & _KERNEL_DIRS)
        for node in pf.nodes:
            if not isinstance(node, ast.Call):
                continue
            resolved = pf.imports.resolve_node(node.func) or ""
            f = self._check_rng(pf, node, resolved)
            if f is None and in_kernel_code:
                f = self._check_wallclock(pf, node, resolved)
            if f is not None:
                out.append(f)
        return out

    def _check_rng(self, pf: ParsedFile, node: ast.Call,
                   resolved: str) -> Optional[Finding]:
        if resolved.startswith("numpy.random."):
            attr = resolved.split(".")[-1]
            if attr in _NP_LEGACY:
                return self._finding(
                    pf, node,
                    f"legacy global numpy RNG ({resolved}); hidden "
                    f"process-wide state breaks replayability",
                    "use np.random.default_rng(seed) (or the jax key "
                    "streams) — see the seeded streams in "
                    "gbdt/trainer.py")
            if attr == "default_rng" and not node.args \
                    and not node.keywords:
                return self._finding(
                    pf, node,
                    "np.random.default_rng() without a seed is fresh "
                    "entropy per call",
                    "thread an explicit seed (estimators derive one "
                    "from their `seed` param)")
        if resolved.startswith("random."):
            attr = resolved.split(".")[-1]
            if attr == "Random" and not node.args and not node.keywords:
                return self._finding(
                    pf, node, "random.Random() without a seed",
                    "pass an explicit seed")
            if attr in _STDLIB_RANDOM and resolved.count(".") == 1:
                return self._finding(
                    pf, node,
                    f"stdlib global RNG ({resolved}) draws from hidden "
                    f"process state",
                    "use a seeded random.Random(seed) instance (see "
                    "core/retries.py jitter)")
        return None

    def _check_wallclock(self, pf: ParsedFile, node: ast.Call,
                         resolved: str) -> Optional[Finding]:
        if resolved in _WALLCLOCK or resolved.endswith(
                _WALLCLOCK_SUFFIXES):
            return self._finding(
                pf, node,
                f"wall-clock ({resolved}) in kernel/trainer code makes "
                f"results or cache keys time-dependent",
                "move timing to the host driver (core/timer.py "
                "StopWatch) or derive from the iteration counter")
        return None

    def _finding(self, pf: ParsedFile, node: ast.AST, message: str,
                 hint: str) -> Finding:
        return Finding(rule=self.rule, severity="warning", path=pf.rel,
                       line=node.lineno, col=node.col_offset,
                       message=message, hint=hint)
