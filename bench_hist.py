"""Microbenchmark: GBDT histogram formulations at bench scale.

The per-level histogram (binned (N,F) + grad/hess/live -> (width,F,B,3))
is the flagship trainer's hot op (SURVEY.md §2.7 row 1). This script
measures the candidate XLA formulations on the current backend so the
trainer can adopt the winner per hardware:

  A. stacked   — one segment_sum over (N*F, 3) rows (reachable only
                 via MMLSPARK_TPU_HIST_FORMULATION=fused)
  B. separate  — three scalar segment_sums sharing the index vector
                 (trainer default under shard_map on TPU)
  C. per-feat  — fori_loop over features, (N, 3) segments each
                 (trainer default outside shard_map)
  D. scatter   — zeros.at[idx].add on the flat (width*F*B, 3) table
  E. onehot    — chunked one-hot contraction on the MXU (pure-XLA
                 insurance for the Pallas kernel; env-selectable)
  F. pallas    — the Mosaic kernel (TPU only)

Run: python bench_hist.py [N] (default 2_000_000; JAX_PLATFORMS=cpu for
a CPU run). Prints one JSON line per variant.
"""

import json
import sys
import time

import numpy as np


def main():
    from bench import device_stamp
    stamp = device_stamp()
    import jax
    import jax.numpy as jnp

    cli_args = [a for a in sys.argv[1:] if not a.startswith("--")]
    n = int(cli_args[0]) if cli_args else 2_000_000
    f, b, width = 28, 255, 32
    rng = np.random.default_rng(0)
    binned = jnp.asarray(rng.integers(0, b, size=(n, f), dtype=np.int32)
                         .astype(np.uint8))
    grad = jnp.asarray(rng.normal(size=n).astype(np.float32))
    hess = jnp.asarray(rng.uniform(0.1, 1.0, size=n).astype(np.float32))
    live = jnp.asarray((rng.random(n) < 0.9).astype(np.float32))
    local = jnp.asarray(rng.integers(0, width, size=n, dtype=np.int32))

    def idx_flat(binned, local):
        base = (local[:, None] * f + jnp.arange(f)[None, :]) * b
        return (base + binned).reshape(-1)

    def variant_stacked(binned, grad, hess, live, local):
        idx = idx_flat(binned, local)
        data = jnp.stack([
            jnp.broadcast_to((grad * live)[:, None], (n, f)).reshape(-1),
            jnp.broadcast_to((hess * live)[:, None], (n, f)).reshape(-1),
            jnp.broadcast_to(live[:, None], (n, f)).reshape(-1),
        ], axis=-1)
        return jax.ops.segment_sum(data, idx,
                                   num_segments=width * f * b)

    def variant_separate(binned, grad, hess, live, local):
        idx = idx_flat(binned, local)
        outs = []
        for chan in (grad * live, hess * live, live):
            flat = jnp.broadcast_to(chan[:, None], (n, f)).reshape(-1)
            outs.append(jax.ops.segment_sum(flat, idx,
                                            num_segments=width * f * b))
        return jnp.stack(outs, axis=-1)

    def variant_per_feature(binned, grad, hess, live, local):
        data = jnp.stack([grad * live, hess * live, live], axis=-1)

        def body(fi, acc):
            idx = (local * b + binned[:, fi].astype(jnp.int32)
                   ).astype(jnp.int32)
            h = jax.ops.segment_sum(data, idx, num_segments=width * b)
            return acc.at[:, fi].set(h.reshape(width, b, 3))

        acc = jnp.zeros((width, f, b, 3), jnp.float32)
        return jax.lax.fori_loop(0, f, body, acc)

    def variant_scatter(binned, grad, hess, live, local):
        idx = idx_flat(binned, local)
        data = jnp.stack([
            jnp.broadcast_to((grad * live)[:, None], (n, f)).reshape(-1),
            jnp.broadcast_to((hess * live)[:, None], (n, f)).reshape(-1),
            jnp.broadcast_to(live[:, None], (n, f)).reshape(-1),
        ], axis=-1)
        return jnp.zeros((width * f * b, 3), jnp.float32).at[idx].add(data)

    def variant_pallas(binned, grad, hess, live, local):
        from mmlspark_tpu.models.gbdt.hist_pallas import (
            pallas_level_histogram,
        )
        return pallas_level_histogram(binned, grad, hess, live, local,
                                      width, f, b)

    def variant_per_feature_unrolled(binned, grad, hess, live, local):
        # same math as per_feature but as 28 INDEPENDENT segment_sums
        # (no loop carry): lets XLA schedule/overlap the scatters
        # instead of serializing them through a fori_loop
        data = jnp.stack([grad * live, hess * live, live], axis=-1)
        outs = []
        for fi in range(f):
            idx = local * b + binned[:, fi].astype(jnp.int32)
            outs.append(jax.ops.segment_sum(
                data, idx, num_segments=width * b).reshape(width, b, 3))
        return jnp.stack(outs, axis=1)

    def variant_onehot(binned, grad, hess, live, local):
        import os

        from mmlspark_tpu.models.gbdt.trainer import _level_histogram
        os.environ["MMLSPARK_TPU_HIST_FORMULATION"] = "onehot"
        try:
            return _level_histogram(binned, grad, hess, live, local,
                                    width, f, b, allow_pallas=False)
        finally:
            os.environ.pop("MMLSPARK_TPU_HIST_FORMULATION", None)

    def variant_native(binned, grad, hess, live, local):
        # the cache-blocked C++ kernel through the same pure_callback
        # the trainer dispatches (CPU-backend default)
        from mmlspark_tpu.models.gbdt.trainer import (
            _native_level_histogram)
        return _native_level_histogram(binned, grad, hess, live, local,
                                       width, f, b)

    # Order = measurement priority: the most decision-relevant
    # variants go first (scatter hung the retired stack's compile and
    # goes dead last). A hung compile starves every later variant in
    # the same process, so a chip run takes subsets in
    # separately-timeboxed steps via --only=name1,name2.
    variants = {"pallas": variant_pallas,
                "native": variant_native,
                "onehot": variant_onehot,
                "per_feature": variant_per_feature,
                "per_feature_unrolled": variant_per_feature_unrolled,
                "separate": variant_separate,
                "stacked": variant_stacked,
                "scatter": variant_scatter}
    only = [a.split("=", 1)[1] for a in sys.argv[1:]
            if a.startswith("--only=")]
    if only:
        requested = [s for s in only[0].split(",") if s]
        unknown = [s for s in requested if s not in variants]
        if unknown:
            raise SystemExit(f"unknown --only variants: {unknown}; "
                             f"have {list(variants)}")
        variants = {k: variants[k] for k in requested}
    if jax.default_backend() != "tpu" and "pallas" in variants:
        # interpret-mode pallas at bench scale is not a measurement
        variants.pop("pallas")
    if jax.default_backend() == "tpu" and "native" in variants and not only:
        # a host callback on TPU measures PCIe transfer, not the
        # kernel; don't burn TPU-window time on it unless asked
        variants.pop("native")
    if not variants:
        print(json.dumps({"note": "no runnable variants on this "
                          "backend for the requested --only set"}))
        return
    results = {}
    fn_args = (binned, grad, hess, live, local)
    for name, fn in variants.items():
        # arrays go in as ARGUMENTS: closure capture would embed them
        # as jaxpr constants and XLA may then CONSTANT-FOLD the whole
        # variant at compile time (observed: the unrolled scatters were
        # folded on CPU, "measuring" a memcpy)
        jitted = jax.jit(fn)
        try:
            jitted(*fn_args)[0].block_until_ready()  # compile
            reps = 5
            t0 = time.perf_counter()
            for _ in range(reps):
                out = jitted(*fn_args)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / reps
        except Exception as e:  # a variant may not lower on a backend
            print(json.dumps({"variant": name, "error": str(e)[:400]}),
                  flush=True)
            continue
        results[name] = dt
        print(json.dumps({
            "variant": name, "seconds_per_level": round(dt, 5),
            "rows_per_s_M": round(n / dt / 1e6, 1),
            "backend": jax.default_backend(), **stamp}), flush=True)
    if results:
        best = min(results, key=results.get)
        stacked = results.get("stacked")
        print(json.dumps({
            "best": best,
            "speedup_vs_stacked": (round(stacked / results[best], 2)
                                   if stacked else None)}), flush=True)


if __name__ == "__main__":
    main()
