"""Centralized, typed environment-variable access.

Every ``MMLSPARK_TPU_*`` knob the framework reads is declared ONCE in
the :data:`REGISTRY` below and read through the typed helpers
(:func:`env_flag` / :func:`env_int` / :func:`env_float` /
:func:`env_str` / :func:`env_raw`).
This is the single source of truth that the graftlint GL004 checker
(tools/graftlint) reconciles against PARAMS.md and README.md, so a knob
cannot ship undocumented and a doc row cannot outlive its code.

Raw ``os.environ`` access to ``MMLSPARK_TPU_*`` names anywhere else in
the package is a lint error (GL004); non-framework variables (JAX_*,
XLA_*, platform detection) are out of scope and stay where they are.

Parsing contract (shared with the pre-existing knobs, see
``resolve_hist_quant``'s bad-value handling): a malformed
value must not abort — or silently mislabel — a run, so ``env_flag`` /
``env_int`` warn once per variable and fall back to the default instead
of raising.
"""

from __future__ import annotations

import os
import warnings
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Dict, Iterator, Optional, Set

_TRUTHY = frozenset(("1", "true", "yes", "on"))
_FALSEY = frozenset(("0", "false", "off", "no"))


@dataclass(frozen=True)
class EnvVar:
    """One declared knob: parse kind, default, one-line effect."""

    name: str
    kind: str            # "flag" | "int" | "float" | "str"
    default: object
    description: str


REGISTRY: Dict[str, EnvVar] = {}


def register(name: str, kind: str, default: object,
             description: str) -> str:
    """Declare a knob; returns ``name`` so declarations double as
    importable constants. GL004 parses these literal registrations as
    the code-side env-var inventory."""
    REGISTRY[name] = EnvVar(name, kind, default, description)
    return name


# --- the one registry (keep PARAMS.md "Engine knobs" tables in sync;
# --- GL004 fails the build when they drift) ---------------------------
NATIVE_HIST = register(
    "MMLSPARK_TPU_NATIVE_HIST", "flag", True,
    "=0 disables the native C++ CPU histogram default (back to XLA)")
HIST_SUB = register(
    "MMLSPARK_TPU_HIST_SUB", "str", "",
    "1/0 force the histogram-subtraction trick on/off; unset = native-"
    "kernel-only default")
PALLAS_HIST = register(
    "MMLSPARK_TPU_PALLAS_HIST", "flag", None,
    "Pallas TPU histogram kernel: default ON on the TPU backend (the "
    "sharded reduction no longer assumes a replicated histogram), off "
    "elsewhere; =1/=0 force")
PALLAS_FORCE_COMPILE = register(
    "MMLSPARK_TPU_PALLAS_FORCE_COMPILE", "flag", False,
    "=1 compiles Pallas kernels through Mosaic even off-TPU (AOT "
    "lowering tests / TPU-day debugging) instead of interpret mode")
FLASH = register(
    "MMLSPARK_TPU_FLASH", "flag", False,
    "=1 opts into the Pallas flash-attention kernel on TPU")
DIST_INIT_RETRIES = register(
    "MMLSPARK_TPU_DIST_INIT_RETRIES", "int", 3,
    "total rendezvous attempts in distributed_init")
FAULTS = register(
    "MMLSPARK_TPU_FAULTS", "str", "",
    "arm fault-injection points: comma-separated "
    "point:action[:nth[:param]]")
FABRIC_ENDPOINT = register(
    "MMLSPARK_TPU_FABRIC_ENDPOINT", "str", None,
    "telemetry endpoint URL for certified events (unset: events stay "
    "in the in-process sink)")
FABRIC_TOKEN = register(
    "MMLSPARK_TPU_FABRIC_TOKEN", "str", None,
    "bearer token for the telemetry endpoint")
SAN = register(
    "MMLSPARK_TPU_SAN", "flag", False,
    "=1 enables the graftsan runtime SPMD sanitizer: NaN/Inf "
    "jit-boundary guards, collective-sequence cross-checks, "
    "recompilation budget (core/sanitizer.py)")
SAN_RECOMPILE_BUDGET = register(
    "MMLSPARK_TPU_SAN_RECOMPILE_BUDGET", "int", 0,
    "with graftsan enabled: max compilations per process before "
    "RecompileBudgetExceeded (0 = count only, never raise)")
SAN_LOCK_HOLD_MS = register(
    "MMLSPARK_TPU_SAN_LOCK_HOLD_MS", "float", 0.0,
    "with graftsan enabled: warn (SanLockHoldWarning) when a san_lock "
    "is held longer than this many milliseconds, naming the acquire "
    "site (0 = hold-time check off; order-inversion detection is "
    "always on under MMLSPARK_TPU_SAN=1)")
SAN_DTYPE = register(
    "MMLSPARK_TPU_SAN_DTYPE", "flag", True,
    "with graftsan enabled: record dtype-signature contracts at parity "
    "boundaries and raise DtypeDrift on signature change (=0 keeps "
    "MMLSPARK_TPU_SAN=1 but turns only the dtype-contract check off)")
HIST_QUANT = register(
    "MMLSPARK_TPU_HIST_QUANT", "str", "off",
    "gradient/hessian quantization for histogram construction: "
    "off|q16|q8; shared per-round pow2 scale, int32 accumulation with "
    "periodic rescale (arXiv:2011.02022)")
EFB = register(
    "MMLSPARK_TPU_EFB", "str", "auto",
    "exclusive feature bundling for histogram construction: auto|off|on"
    " — auto gates the planner on a sampled sparsity estimate, on "
    "forces planning even for dense-looking data")
HIST_SHARD = register(
    "MMLSPARK_TPU_HIST_SHARD", "str", "auto",
    "data-parallel histogram reduction sharding: auto|off|on — "
    "reduce-scatter (psum_scatter) the per-level histogram across dp "
    "so each replica owns a feature slice and selects its splits "
    "locally (arXiv:2004.13336); auto enables it when dp>1 and the "
    "config supports it, on forces (warn-once downgrade when "
    "unsupported), off keeps the full-psum GSPMD path")
GROW_POLICY = register(
    "MMLSPARK_TPU_GROW_POLICY", "str", "depthwise",
    "tree growth policy: depthwise|leafwise; leafwise drives splits by "
    "a max-gain priority queue capped by num_leaves")
SERVE_BINNED = register(
    "MMLSPARK_TPU_SERVE_BINNED", "str", "auto",
    "serving binned data plane: auto|off|on — pre-bin request rows to "
    "the binned ingest dtype on the request threads and score through "
    "predict_binned_jit at bucket-padded shapes; auto activates when "
    "the served model supports it, on warns once (reason in /healthz) "
    "when it cannot, off keeps the generic transform path")
SERVE_BUCKETS = register(
    "MMLSPARK_TPU_SERVE_BUCKETS", "str", "",
    "comma-separated batch-size bucket ladder for the serving data "
    "plane (the padded compile shapes, pre-warmed at start); empty = "
    "powers of two up to max_batch_size")
SERVE_MODEL_QUEUE = register(
    "MMLSPARK_TPU_SERVE_MODEL_QUEUE", "int", 0,
    "per-model pending-queue cap in a multi-model ServingServer "
    "(0 = the server-wide max_queue applies to each model)")
SERVE_WARM_MODELS = register(
    "MMLSPARK_TPU_SERVE_WARM_MODELS", "int", 4,
    "how many served models keep compiled scorers resident (LRU); a "
    "model evicted cold drops its compiled plane + jit cache and "
    "rebuilds lazily on next use")
SHARD_RULES = register(
    "MMLSPARK_TPU_SHARD_RULES", "str", "auto",
    "regex-rule sharding for transform/inference: auto|off|on — auto "
    "applies the per-family PartitionSpec rule table whenever the "
    "model carries a mesh, on warns once when no mesh is attached "
    "(serial fallback), off forces the serial single-device path")
INFER_AUTOCAST = register(
    "MMLSPARK_TPU_INFER_AUTOCAST", "str", "off",
    "inference weight autocast for the shard-rules engine: off|bf16 — "
    "bf16 casts resident float weights at shard time (off is the "
    "default and the bitwise-parity-pinned arm)")
TRAIN_SHARD = register(
    "MMLSPARK_TPU_TRAIN_SHARD", "str", "auto",
    "ZeRO-1 sharded training state for the dl fit loop: auto|off|on — "
    "partition optimizer moments (and the weight update) across dp via "
    "the DL_TRAIN_RULES table, reduce-scatter grads and all-gather "
    "updated params (arXiv:2004.13336); auto activates when the fit "
    "mesh has a dp axis, on warns once when it cannot, off keeps the "
    "fully replicated update")
PREFETCH_DEPTH = register(
    "MMLSPARK_TPU_PREFETCH_DEPTH", "int", 2,
    "batches the async input pipeline (parallel/prefetch.py) stages "
    "ahead of the training step on a background thread (device_put "
    "overlapped with compute); 0 disables the thread and feeds batches "
    "synchronously")
STREAM_BUFFER = register(
    "MMLSPARK_TPU_STREAM_BUFFER", "int", 65536,
    "bounded ingestion-buffer capacity (rows) for the streaming "
    "refresh loop (io/refresh.py); a full buffer blocks the producer "
    "(backpressure) instead of growing without bound")
REFRESH_INTERVAL_S = register(
    "MMLSPARK_TPU_REFRESH_INTERVAL_S", "int", 300,
    "streaming refresh loop: seconds between time-based refit checks "
    "(a refit arms when the interval elapsed and the buffer holds "
    "enough rows; detected drift arms one sooner)")
REFRESH_PRIORITY = register(
    "MMLSPARK_TPU_REFRESH_PRIORITY", "str", "low",
    "co-located refresh loop priority (io/refresh.py): 'low' installs "
    "the train-step throttle for the refit, which yields whenever the "
    "bound server's serving queue crosses its high-water mark (a "
    "background refit cannot starve the data plane); 'high' refits at "
    "full speed")
REFRESH_YIELD_S = register(
    "MMLSPARK_TPU_REFRESH_YIELD_S", "float", 2.0,
    "max seconds a low-priority refit yields at any one train-step "
    "boundary while the co-located serving queue stays past high "
    "water; the refit then takes its step anyway (forward progress "
    "beats perfect politeness)")
DRIFT_THRESHOLD = register(
    "MMLSPARK_TPU_DRIFT_THRESHOLD", "float", 0.2,
    "drift-detector arm level for the max per-feature statistic "
    "(PSI default 0.2, the standard significant-shift level; for the "
    "ks metric pick ~0.1-0.15) — exploratory/drift.py")
FLEET_MIN = register(
    "MMLSPARK_TPU_FLEET_MIN", "int", 1,
    "elastic serving fleet: minimum worker count the FleetSupervisor "
    "retires down to (io/fleet.py)")
FLEET_MAX = register(
    "MMLSPARK_TPU_FLEET_MAX", "int", 4,
    "elastic serving fleet: maximum worker count the FleetSupervisor "
    "scales up to")
FLEET_SCALE_P99_MS = register(
    "MMLSPARK_TPU_FLEET_SCALE_P99_MS", "float", 250.0,
    "elastic serving fleet: worker p99 latency (ms) above which the "
    "supervisor arms a scale-up; scale-down arms below a quarter of it "
    "(hysteresis)")
FLEET_COOLDOWN_S = register(
    "MMLSPARK_TPU_FLEET_COOLDOWN_S", "float", 10.0,
    "elastic serving fleet: seconds after any scaling action before "
    "the next one may fire (flap damping)")
FLEET_HEARTBEAT_S = register(
    "MMLSPARK_TPU_FLEET_HEARTBEAT_S", "float", 1.0,
    "elastic serving fleet: seconds between supervisor /healthz "
    "heartbeat sweeps; K consecutive missed heartbeats mark a worker "
    "dead")
SERVE_TENANT_RATE = register(
    "MMLSPARK_TPU_SERVE_TENANT_RATE", "float", 0.0,
    "serving admission control: per-tenant token-bucket refill rate in "
    "requests/s (tenant from the __tenant__ payload field or X-Tenant "
    "header; 0 = admission token buckets off)")
SERVE_TENANT_BURST = register(
    "MMLSPARK_TPU_SERVE_TENANT_BURST", "int", 8,
    "serving admission control: per-tenant token-bucket capacity "
    "(burst size); an over-budget tenant sheds with 503 + Retry-After "
    "without dragging other tenants' p99")
REQUEST_DEADLINE_MS = register(
    "MMLSPARK_TPU_REQUEST_DEADLINE_MS", "float", 0.0,
    "gray-failure tolerance: end-to-end request budget in ms that "
    "FleetClient stamps as the X-Deadline-Ms header; the remaining "
    "budget rides the queue and the server sheds already-expired "
    "requests at dequeue with an attributed 504 before scoring "
    "(0 = no deadline propagation)")
HEDGE_DELAY_MS = register(
    "MMLSPARK_TPU_HEDGE_DELAY_MS", "float", 30.0,
    "gray-failure tolerance: floor in ms on FleetClient's adaptive "
    "hedge delay (rolling per-worker p95); after the delay without a "
    "reply the request is hedged on a second worker and the first "
    "reply wins")
HEDGE_BUDGET_PCT = register(
    "MMLSPARK_TPU_HEDGE_BUDGET_PCT", "float", 5.0,
    "gray-failure tolerance: hedge token bucket — hedged requests may "
    "add at most this percentage of extra backend load (a hedge costs "
    "one token; tokens accrue per primary request)")
RETRY_BUDGET_PCT = register(
    "MMLSPARK_TPU_RETRY_BUDGET_PCT", "float", 10.0,
    "gray-failure tolerance: global FleetClient retry token bucket as "
    "a percentage of request volume; once drained (fleet-wide "
    "brownout) further retries shed to the caller with attribution "
    "instead of amplifying the overload")
WATCHDOG_MULT = register(
    "MMLSPARK_TPU_WATCHDOG_MULT", "float", 0.0,
    "train-step watchdog: stall budget multiplier over the rolling p99 "
    "step time (budget = max(p99 * MULT, WATCHDOG_MIN_S)); 0 disables "
    "the watchdog (default — disabled hooks cost one None check)")
WATCHDOG_MIN_S = register(
    "MMLSPARK_TPU_WATCHDOG_MIN_S", "float", 60.0,
    "train-step watchdog: floor on the stall budget in seconds; must "
    "exceed the longest legitimate sync span (a fused-scan fit lands "
    "nearly all compute in the final drain span)")
WATCHDOG_INIT_S = register(
    "MMLSPARK_TPU_WATCHDOG_INIT_S", "float", 0.0,
    "fixed stall budget in seconds for each distributed_init attempt "
    "(an init that never returns); expiry raises an attributed "
    "TrainStalled instead of hanging; 0 disables (default)")
RECOVERY_MAX = register(
    "MMLSPARK_TPU_RECOVERY_MAX", "int", 2,
    "fit_resilient: maximum dp-shrink recovery attempts before the "
    "original error is re-raised")
RECOVERY_MIN_DP = register(
    "MMLSPARK_TPU_RECOVERY_MIN_DP", "int", 1,
    "fit_resilient: smallest dp slice worth re-forming; a failure at "
    "this size is re-raised instead of recovered")
OOC = register(
    "MMLSPARK_TPU_OOC", "str", "auto",
    "out-of-core GBDT training: auto (engage when the row count "
    "reaches MMLSPARK_TPU_OOC_ROWS), on (force; warn-once downgrade "
    "to in-core when the fit shape is unsupported), off")
OOC_ROWS = register(
    "MMLSPARK_TPU_OOC_ROWS", "int", 4_000_000,
    "out-of-core training: row threshold at which MMLSPARK_TPU_OOC="
    "auto switches a supported fit to the chunked spill plane")
OOC_CHUNK_ROWS = register(
    "MMLSPARK_TPU_OOC_CHUNK_ROWS", "int", 262_144,
    "out-of-core training: rows per spill chunk; peak training RSS "
    "scales with this (chunk working set), not with the dataset")
SPILL_VERIFY = register(
    "MMLSPARK_TPU_SPILL_VERIFY", "str", "auto",
    "integrity verification for on-disk artifacts: auto|off|on — auto "
    "(default) always verifies checkpoint payload digests and checks "
    "each spill/chunk-store chunk's crc32 on its first read (and "
    "after every rewrite), on verifies every read, off trusts the "
    "disk; verification cost is stamped in hist_stats")
CHAOSFUZZ_BUDGET_S = register(
    "MMLSPARK_TPU_CHAOSFUZZ_BUDGET_S", "float", 30.0,
    "tools/chaosfuzz: per-schedule wall-clock watchdog budget in "
    "seconds (the stall_guard backstop) — a scenario still running "
    "past it is recorded as a hang violation, never an indefinite "
    "hang; --budget overrides")


_WARNED: Set[str] = set()


def _warn_once(name: str, message: str) -> None:
    if name in _WARNED:
        return
    _WARNED.add(name)
    warnings.warn(message, stacklevel=3)


def reset_warnings() -> None:
    """Forget which variables already warned (test hook)."""
    _WARNED.clear()


def env_raw(name: str) -> Optional[str]:
    """The unparsed value, ``None`` when unset. For cache keys that must
    distinguish unset from every set value."""
    return os.environ.get(name)


def env_flag(name: str, default: bool = False) -> bool:
    """Boolean knob: 1/true/yes/on -> True, 0/false/off/no -> False
    (case-insensitive); unset/empty -> ``default``; anything else warns
    once and returns ``default``."""
    v = os.environ.get(name)
    if v is None:
        return default
    v = v.strip().lower()
    if not v:
        return default
    if v in _TRUTHY:
        return True
    if v in _FALSEY:
        return False
    _warn_once(name, f"{name}={v!r} is not a recognized boolean "
                     f"(1/true/yes/on or 0/false/off/no); using "
                     f"{default}")
    return default


def env_int(name: str, default: int, minimum: Optional[int] = None) -> int:
    """Integer knob; a non-integer or below-``minimum`` value warns once
    and returns ``default`` (a bad value must not abort — or silently
    mislabel — a measurement run)."""
    v = os.environ.get(name)
    if v is None or not v.strip():
        return default
    try:
        value = int(v.strip())
    except ValueError:
        _warn_once(name, f"{name}={v!r} is not an integer; using "
                         f"{default}")
        return default
    if minimum is not None and value < minimum:
        _warn_once(name, f"{name}={value} is below the minimum "
                         f"{minimum}; using {default}")
        return default
    return value


def env_float(name: str, default: float,
              minimum: Optional[float] = None) -> float:
    """Float knob; same degradation contract as :func:`env_int` — a
    non-numeric or below-``minimum`` value warns once and returns
    ``default``."""
    v = os.environ.get(name)
    if v is None or not v.strip():
        return default
    try:
        value = float(v.strip())
    except ValueError:
        _warn_once(name, f"{name}={v!r} is not a number; using "
                         f"{default}")
        return default
    if minimum is not None and value < minimum:
        _warn_once(name, f"{name}={value} is below the minimum "
                         f"{minimum}; using {default}")
        return default
    return value


def env_str(name: str, default: Optional[str] = None) -> Optional[str]:
    """String knob, unstripped (callers strip/validate as needed)."""
    v = os.environ.get(name)
    return default if v is None else v


@contextmanager
def env_override(name: str, value: Optional[str]) -> Iterator[None]:
    """Temporarily set (or, with ``None``, unset) a variable, restoring
    the previous state on exit — the sanctioned way to scope an env
    knob around a block (e.g. AOT lowering forcing the non-callback
    histogram)."""
    prev = os.environ.get(name)
    if value is None:
        os.environ.pop(name, None)
    else:
        os.environ[name] = value
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = prev
