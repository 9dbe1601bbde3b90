"""One level's routing on the chip, by form and width: the measurement
behind ``trainer.route_form`` (PERF.md §6, PR 34).

usage: python3 tools/route_level_ab.py [--rows N] [--features F]
           [--widths 1,8,32] [--forms gather,select,features] [--reps 3]
           [--out chiprun_out/route_level_ab.jsonl]

``gather`` and ``select`` are ``trainer.route_level``'s two forms.
``features`` is the other select PR 34 weighed, kept here only to be
measured against: the row's feature chosen over the level's nodes, then
its bin over the matrix's F columns in one expression. XLA unpacks every
column it selects among into a vector of its own first, so that form
takes 0.030-0.032 s a level at every width and 0.52-1.16 GB of scratch
at 20M x 28 for widths 1 to 32, where the loop over the nodes takes
0.002-0.040 s and 20 MB (my chip run, PR 34). Arrays are made once from a seed and passed
as arguments. One JSON line a (form, width): the call's host-clock seconds
(block_until_ready), the program's scratch bytes, and whether the form
gave every row the node ``gather`` gave. Exits 2 off the TPU unless
JAX_PLATFORMS=cpu is set (a rehearsal: times meaningless)."""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def route_over_features(binned, node, done, local, do_split, best_feat,
                        best_bin):
    import jax.numpy as jnp

    def by_node(table):
        out = jnp.zeros(local.shape, table.dtype)
        for w in range(table.shape[0]):
            out = jnp.where(local == w, table[w], out)
        return out

    nfeat, thr, nsplit = by_node(best_feat), by_node(best_bin), by_node(do_split)
    nbin = jnp.zeros(local.shape, binned.dtype)
    for j in range(binned.shape[1]):
        nbin = jnp.where(nfeat == j, binned[:, j], nbin)
    child = jnp.where(nbin <= thr, 2 * node + 1, 2 * node + 2)
    return jnp.where(done | ~nsplit, node, child), done | ~nsplit


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=20_000_000)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--bins", type=int, default=255)
    ap.add_argument("--widths", default="1,8,32")
    ap.add_argument("--forms", default="gather,select,features")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default="chiprun_out/route_level_ab.jsonl")
    args = ap.parse_args(argv)

    import functools

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("no TPU; set JAX_PLATFORMS=cpu to rehearse", file=sys.stderr)
        return 2

    from mmlspark_tpu.models.gbdt.trainer import route_level

    n, f, b = args.rows, args.features, args.bins
    rng = np.random.default_rng(args.seed)
    binned = jnp.asarray(rng.integers(0, b, size=(n, f), dtype=np.uint8))
    done = jnp.asarray(rng.random(n) < 0.1)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    ok = True
    with open(args.out, "a") as sink:
        for width in [int(w) for w in args.widths.split(",")]:
            local_h = rng.integers(0, width, size=n, dtype=np.int32)
            ops = (binned, jnp.asarray(local_h + width - 1), done,
                   jnp.asarray(local_h),
                   jnp.asarray(rng.random(width) < 0.8),
                   jnp.asarray(rng.integers(0, f, width, dtype=np.int32)),
                   jnp.asarray(rng.integers(0, b - 1, width, dtype=np.int32)))
            want = None
            for form in args.forms.split(","):
                fn = jax.jit(route_over_features if form == "features"
                             else functools.partial(route_level, form=form))
                row = {"form": form, "width": width, "rows": n,
                       "features": f, "platform": dev.platform,
                       "device_kind": dev.device_kind}
                try:
                    compiled = fn.lower(*ops).compile()
                    row["temp_bytes"] = (
                        compiled.memory_analysis().temp_size_in_bytes)
                    got = jax.block_until_ready(compiled(*ops))
                    secs = []
                    for _ in range(args.reps):
                        t = time.perf_counter()
                        jax.block_until_ready(compiled(*ops))
                        secs.append(time.perf_counter() - t)
                    row["call_s"] = sorted(secs)
                    got = [np.asarray(a) for a in got]
                    if form == "gather":
                        want = got
                    elif want is not None:
                        row["equals_gather"] = all(
                            np.array_equal(a, w) for a, w in zip(got, want))
                        ok &= row["equals_gather"]
                except Exception as e:  # a form the chip cannot hold
                    row["error"] = f"{type(e).__name__}: {e}"[:600]
                line = json.dumps(row)
                print(line, flush=True)
                sink.write(line + "\n")
                sink.flush()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
