"""The latent decode and the one-token cache write on the chip, kernel
against ``jax.numpy`` twin and scatter against slices, by shape: the
measurement behind ``latent.DECODE_ROWS`` and ``DECODE_BLOCK`` (PERF.md
section 6, PR 35).

usage: python3 tools/latent_decode_ab.py [--shapes 512x1280,128x768]
           [--tiles 8x128,8x256,4x256,16x128] [--fill 0.5] [--steps 20]
           [--out chiprun_out/latent_decode_ab.jsonl]

A shape is rows x capacity at the published widths (64 heads, rank 512,
rope 64, bfloat16). Rows' positions are a sorted ramp about ``fill`` of
the capacity (a length-sorted batch in the middle of a call). Each
variant runs ``steps`` steps inside one ``jax.jit`` over a ``lax.scan``
whose carry is the cache, as ``lm_generate`` does. One JSON line a
variant: seconds a step (host clock around the whole scan, less one
step's), the floor of ``benchmark/opcount_latent_lm.latent_decode``,
and the largest difference from the twin inside that scan. Exits 2 off
the TPU unless JAX_PLATFORMS=cpu is set (a rehearsal: Pallas
interpreted, times meaningless)."""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HEADS, RANK, ROPE, NOPE, DV = 64, 512, 64, 128, 128


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="512x1280,128x768")
    ap.add_argument("--tiles", default="8x128,8x256,4x256,16x128")
    ap.add_argument("--fill", type=float, default=0.5)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/latent_decode_ab.jsonl")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel import latent

    rehearsal = jax.default_backend() != "tpu"
    if rehearsal and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("latent_decode_ab: needs the TPU (or JAX_PLATFORMS=cpu for a "
              "rehearsal)", file=sys.stderr)
        return 2
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    bf = jnp.bfloat16
    lines = []

    def timed(fn, *xs):
        out = jax.block_until_ready(fn(*xs))        # compiles
        t = time.perf_counter()
        out = jax.block_until_ready(fn(*xs))
        return time.perf_counter() - t, out

    for shape in args.shapes.split(","):
        b, cap = (int(v) for v in shape.split("x"))
        key = jax.random.split(jax.random.PRNGKey(b + cap), 8)
        cache = {"c": jax.random.normal(key[0], (b, cap, RANK), bf),
                 "r": jax.random.normal(key[1], (b, cap, ROPE), bf)}
        q_n = jax.random.normal(key[2], (b, HEADS, NOPE), jnp.float32)
        q_r = jax.random.normal(key[3], (b, HEADS, ROPE), jnp.float32)
        w_uk = (jax.random.normal(key[4], (RANK, HEADS, NOPE)) * .05).astype(bf)
        w_uv = (jax.random.normal(key[5], (RANK, HEADS, DV)) * .05).astype(bf)
        new_c = jax.random.normal(key[6], (b, 1, RANK), jnp.float32)
        new_r = jax.random.normal(key[7], (b, 1, ROPE), jnp.float32)
        mid = args.fill * cap
        pos0 = jnp.asarray(np.clip(np.sort(np.random.default_rng(0).lognormal(
            np.log(mid), 0.3, b)), 1, cap - args.steps - 1), jnp.int32)

        def scan_of(decode, write, steps):
            def run(cache, pos):
                def step(carry, _):
                    cache, pos = carry
                    if write is not None:
                        cache = write(cache, new_c, new_r, pos,
                                      jnp.ones_like(pos))
                    o = decode(cache, pos) if decode is not None else \
                        cache["c"][:, 0, :8].astype(jnp.float32)
                    return (cache, pos + 1), o
                (cache, _), outs = jax.lax.scan(step, (cache, pos), None,
                                                length=steps)
                return outs, cache["c"][:, :, 0].sum()
            return jax.jit(run)

        def decode_with(pallas):
            return lambda cache, pos: latent.latent_decode(
                q_n, q_r, cache, w_uk, w_uv, pos, scale=0.1, dtype=bf,
                pallas=pallas, interpret=rehearsal and pallas)

        def old_write(cache, c, r, pos, lengths):       # the slice a row
            return latent.cache_write(
                cache, jnp.concatenate([c, c], 1), jnp.concatenate([r, r], 1),
                pos, lengths)

        def per_step(decode, write):
            long, out = timed(scan_of(decode, write, args.steps), cache, pos0)
            short, _ = timed(scan_of(decode, write, 1), cache, pos0)
            return (long - short) / (args.steps - 1), out[0]

        from benchmark import opcount, opcount_latent_lm
        cfg = {"num_attention_heads": HEADS, "kv_lora_rank": RANK,
               "qk_rope_head_dim": ROPE}
        positions = float(np.asarray(pos0).sum() + b)
        flops, nbytes = opcount_latent_lm.latent_decode(cfg, positions)
        floor = (None if rehearsal else opcount.least_seconds(
            flops, nbytes, opcount.peaks(jax.devices()[0].device_kind))[0])
        twin_s, twin = per_step(decode_with(False), None)
        lines.append({"shape": shape, "variant": "twin", "step_s": twin_s,
                      "floor_s": floor, "positions": positions})
        for tile in args.tiles.split(","):
            latent.DECODE_ROWS, latent.DECODE_BLOCK = (
                int(v) for v in tile.split("x"))
            try:
                s, got = per_step(decode_with(True), None)
            except Exception as e:            # what Mosaic refused
                lines.append({"shape": shape, "variant": "kernel " + tile,
                              "refused": str(e)[:300]})
                continue
            lines.append({
                "shape": shape, "variant": "kernel " + tile, "step_s": s,
                "floor_s": floor,
                "max_abs_diff_from_twin": float(jnp.abs(got - twin).max()),
                "twin_scale": float(jnp.abs(twin).max())})
        for name, write in (("scatter", latent.cache_write),
                            ("slices", old_write)):
            s, _ = per_step(None, write)
            lines.append({"shape": shape, "variant": "write " + name,
                          "step_s": s})
        for line in lines[-(3 + len(args.tiles.split(","))):]:
            print(json.dumps(line), flush=True)
    with open(args.out, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
