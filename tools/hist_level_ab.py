"""One level histogram on the chip, both Pallas paths by width: the
measurement that sets ``hist_pallas.IN_PLACE_MAX_WIDTH`` (PERF.md §6,
PR 30; again at PR 38, when the kernel's product became one bf16 pass).

usage: python3 tools/hist_level_ab.py [--rows N] [--features F]
           [--widths 1,8,32,...] [--paths in_place,sorted,chosen]
           [--block-rows 512] [--reps 3] [--no-check] [--truth]
           [--out chiprun_out/hist_level_ab.jsonl]

Arrays are made once from a seed and passed as arguments to each jitted
path (nothing is a compile-time constant). ``in_place`` and ``sorted``
run that path whatever ``level_feed`` would choose (a width or a feature
count past its bounds included: that is how the bounds are read);
``chosen`` runs the kernel's entry and says which path it took (``feed``).
One JSON line a (path, width,
block_rows): the call's host-clock seconds (block_until_ready), the
device seconds of the kernel and of everything else in the program from
one traced call, and the parity against ``per_feature`` (counts exact,
grad/hess rtol 1e-5, atol 1e-4: chip_smoke.py's kernel contract). With
``--truth`` the host also sums the level in float64 and each line says
how far the path and the ``per_feature`` reference each lie from that
(where float32's own rounding over millions of rows a bin passes the
tolerance, this tells which side moved). Exits 2 off the TPU unless
JAX_PLATFORMS=cpu is set (a rehearsal: Pallas interpreted, times
meaningless)."""
import argparse
import functools
import glob
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def device_seconds(trace_dir):
    """Device seconds of one traced call: (kernel, every other op)."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    if not path:
        return None, None
    data = ProfileData.from_file(path[0])
    plane = next((p for p in data.planes
                  if p.name.startswith("/device:TPU:0")), None)
    if plane is None:
        return None, None
    kernel = other = 0.0
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            if "gbdt_level_hist" in ev.name:
                kernel += ev.duration_ns / 1e9
            else:
                other += ev.duration_ns / 1e9
    return kernel, other


def float64_level(binned, grad, hess, live, local, width, b):
    """The level's grad and hess sums in float64 on the host:
    (width, F, b, 2)."""
    n, f = binned.shape
    out = np.empty((width, f, b, 2))
    base = local.astype(np.int64) * 256
    for k, w in enumerate((grad.astype(np.float64) * live,
                           hess.astype(np.float64) * live)):
        for j in range(f):
            out[:, j, :, k] = np.bincount(
                base + binned[:, j], weights=w,
                minlength=width * 256).reshape(width, 256)[:, :b]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=20_000_000)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--bins", type=int, default=255)
    ap.add_argument("--widths", default="1,8,32,64,128,256")
    ap.add_argument("--paths", default="in_place,sorted")
    ap.add_argument("--block-rows", default="512")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--no-check", action="store_true")
    ap.add_argument("--truth", action="store_true")
    ap.add_argument("--out", default="chiprun_out/hist_level_ab.jsonl")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("no TPU; set JAX_PLATFORMS=cpu to rehearse", file=sys.stderr)
        return 2

    from mmlspark_tpu.models.gbdt import hist_pallas
    from mmlspark_tpu.models.gbdt.trainer import _level_histogram

    n, f, b = args.rows, args.features, args.bins
    rng = np.random.default_rng(args.seed)
    host = (rng.integers(0, b, size=(n, f), dtype=np.uint8),
            rng.normal(size=n).astype(np.float32),
            rng.uniform(0.1, 1.0, size=n).astype(np.float32),
            (rng.random(n) < 0.9).astype(np.float32))
    binned, grad, hess, live = (jnp.asarray(a) for a in host)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    ok = True
    with open(args.out, "a") as sink:
        for width in [int(w) for w in args.widths.split(",")]:
            local_h = rng.integers(0, width, size=n, dtype=np.int32)
            local = jnp.asarray(local_h)
            ref = truth = None
            if not args.no_check:
                ref = np.asarray(jax.jit(functools.partial(
                    _level_histogram, width=width, f=f, b=b,
                    formulation="per_feature"))(binned, grad, hess, live,
                                                local))
                if args.truth:
                    truth = float64_level(*host, local_h, width, b)
            for path in args.paths.split(","):
                for r in [int(x) for x in args.block_rows.split(",")]:
                    feed = (hist_pallas.level_feed(width, f)
                            if path == "chosen" else path)
                    fn = jax.jit(functools.partial(
                        hist_pallas._pallas_level_histogram
                        if path == "chosen"
                        else getattr(hist_pallas,
                                     f"_{path}_level_histogram"),
                        width=width, f=f, b=b, block_rows=r,
                        interpret=not on_tpu))
                    row = {"path": path, "feed": feed, "width": width,
                           "block_rows": r,
                           "rows": n, "features": f, "bins": b,
                           "platform": dev.platform,
                           "device_kind": dev.device_kind}
                    try:
                        t = time.perf_counter()
                        out = jax.block_until_ready(
                            fn(binned, grad, hess, live, local))
                        row["first_s"] = time.perf_counter() - t
                        secs = []
                        for _ in range(args.reps):
                            t = time.perf_counter()
                            jax.block_until_ready(
                                fn(binned, grad, hess, live, local))
                            secs.append(time.perf_counter() - t)
                        row["call_s"] = sorted(secs)
                        if on_tpu:
                            tdir = tempfile.mkdtemp(prefix="hist_ab_")
                            with jax.profiler.trace(tdir):
                                jax.block_until_ready(
                                    fn(binned, grad, hess, live, local))
                            row["kernel_s"], row["other_ops_s"] = (
                                device_seconds(tdir))
                            shutil.rmtree(tdir, ignore_errors=True)
                        if ref is not None:
                            got = np.asarray(out)
                            err = np.abs(got[..., :2] - ref[..., :2])
                            tol = 1e-4 + 1e-5 * np.abs(ref[..., :2])
                            row["counts_exact"] = bool(np.array_equal(
                                got[..., 2], ref[..., 2]))
                            row["max_abs_err"] = float(err.max())
                            row["within_tol"] = bool((err <= tol).all())
                            ok &= row["counts_exact"] and row["within_tol"]
                            if truth is not None:
                                row["max_abs_err_vs_float64"] = float(
                                    np.abs(got[..., :2] - truth).max())
                                row["reference_vs_float64"] = float(
                                    np.abs(ref[..., :2] - truth).max())
                        del out
                    except Exception as e:  # a width the chip cannot hold
                        row["error"] = f"{type(e).__name__}: {e}"[:600]
                    line = json.dumps(row)
                    print(line, flush=True)
                    sink.write(line + "\n")
                    sink.flush()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
