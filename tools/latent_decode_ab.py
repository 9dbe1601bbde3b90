"""The latent attention on the chip by form. ``--form decode`` (the
default): the latent decode and the one-token cache write, kernel
against ``jax.numpy`` twin and scatter against slices, by shape: the
measurement behind ``latent.DECODE_ROWS`` and ``DECODE_BLOCK`` (PERF.md
section 6, PR 35). ``--form prefill``: one layer's ``latent_prefill``,
the scan against the kernel by block and tile: the measurement behind
``latent.prefill_form`` and ``PREFILL_KERNEL_MIN_ROWS`` (PERF.md
section 6).

usage: python3 tools/latent_decode_ab.py [--shapes 512x1280,128x768]
           [--tiles 8x128,8x256,4x256,16x128] [--fill 0.5] [--steps 20]
           [--out chiprun_out/latent_decode_ab.jsonl]
       python3 tools/latent_decode_ab.py --form prefill
           [--shapes 4x1024x80x16x16512,32x128x64x64x1280]
           [--tiles 512x512,512x1024x1,128x128x8,rule] [--fill 0.5]
           [--at 0,1,2,3] [--steps 5]

Decode: a shape is rows x capacity at the published widths (64 heads,
rank 512, rope 64, bfloat16). Rows' positions are a sorted ramp about
``fill`` of the capacity (a length-sorted batch in the middle of a
call). Each variant runs ``steps`` steps inside one ``jax.jit`` over a
``lax.scan`` whose carry is the cache, as ``lm_generate`` does. One
JSON line a variant: seconds a step (host clock around the whole scan,
less one step's), the floor of
``benchmark/opcount_latent_lm.latent_decode``, and the largest
difference from the twin inside that scan.

Prefill: a shape is rows x queries x heads x key heads x capacity
(rank 512, nope and d_v 128, rope 64, bfloat16), a form of the kernel
block x queries, and x key heads a grid step where given (else 1);
``rule`` is the form ``latent.prefill_form`` chooses. Every row's
stretch is real; its first position a sorted ramp from half to all of
``fill`` of what the capacity leaves, a multiple of the stretch (a
group of a length-sorted batch at a later step), or with ``--at`` the
same for every row, ``k`` stretches in, once for each ``k`` listed (a
cell's step ``k + 1``: ``--at 0`` is a first step, from position 0).
One JSON line a variant and start: the least of ``steps`` timed calls
of one jitted layer (host clock, each waited for), the floor of its
(query, key) pairs in the expanded form at the bf16 peak, and the
largest difference from the scan.

Exits 2 off the TPU unless JAX_PLATFORMS=cpu is set (a rehearsal:
Pallas interpreted, times meaningless)."""
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
    ap.add_argument("--form", choices=("decode", "prefill"),
                    default="decode")
    ap.add_argument("--shapes")
    ap.add_argument("--tiles")
    ap.add_argument("--fill", type=float, default=0.5)
    ap.add_argument("--at")
    ap.add_argument("--steps", type=int)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    prefill = args.form == "prefill"
    args.shapes = args.shapes or (
        "4x1024x80x16x16512" if prefill else "512x1280,128x768")
    args.tiles = args.tiles or (
        "512x512,512x1024,512x256" if prefill
        else "8x128,8x256,4x256,16x128")
    args.steps = args.steps or (5 if prefill else 20)
    args.out = args.out or f"chiprun_out/latent_{args.form}_ab.jsonl"

    import jax

    rehearsal = jax.default_backend() != "tpu"
    if rehearsal and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("latent_decode_ab: needs the TPU (or JAX_PLATFORMS=cpu for a "
              "rehearsal)", file=sys.stderr)
        return 2
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    lines = (_prefill_ab if prefill else _decode_ab)(args, rehearsal)
    with open(args.out, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    return 0


def _decode_ab(args, rehearsal):
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel import latent

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

        def scatter(cache, c, r, pos, lengths):
            return latent.write_entries(cache, {"c": c, "r": r}, pos, lengths)

        def old_write(cache, c, r, pos, lengths):       # the slice a row
            return latent.write_entries(
                cache, {"c": jnp.concatenate([c, c], 1),
                        "r": jnp.concatenate([r, r], 1)}, pos, lengths)

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
        for name, write in (("scatter", scatter),
                            ("slices", old_write)):
            s, _ = per_step(None, write)
            lines.append({"shape": shape, "variant": "write " + name,
                          "step_s": s})
        for line in lines[-(3 + len(args.tiles.split(","))):]:
            print(json.dumps(line), flush=True)
    return lines


def _prefill_ab(args, rehearsal):
    import jax
    import jax.numpy as jnp

    from benchmark import opcount
    from mmlspark_tpu.parallel import latent

    bf = jnp.bfloat16
    rule = latent.prefill_form
    lines = []
    for shape in args.shapes.split(","):
        b, t, heads, groups, cap = (int(v) for v in shape.split("x"))
        key = jax.random.split(jax.random.PRNGKey(b + t + cap), 6)
        cache = {"c": jax.random.normal(key[0], (b, cap, RANK), bf),
                 "r": jax.random.normal(key[1], (b, cap, ROPE), bf)}
        q_n = jax.random.normal(key[2], (b, t, heads, NOPE), bf)
        q_r = jax.random.normal(key[3], (b, t, heads, ROPE), bf)
        w_uk = (jax.random.normal(key[4], (RANK, groups, NOPE)) * .05
                ).astype(bf)
        w_uv = (jax.random.normal(key[5], (RANK, groups, DV)) * .05
                ).astype(bf)
        lengths = jnp.full((b,), t, jnp.int32)
        if args.at:              # every row at the same step of the cell
            starts = [np.full(b, int(k) * t) for k in args.at.split(",")]
        else:
            room = (cap - t) // t
            starts = [np.round(np.linspace(0.5, 1.0, b) * args.fill * room)
                      * t]
        starts = [jnp.asarray(pos, jnp.int32) for pos in starts]

        def layer(pallas):
            return jax.jit(lambda q_n, q_r, cache, pos: latent.latent_prefill(
                q_n, q_r, cache, w_uk, w_uv, pos, lengths, scale=0.1,
                dtype=bf, pallas=pallas, interpret=rehearsal and pallas))

        def timed(fn, pos):
            out = jax.block_until_ready(fn(q_n, q_r, cache, pos))  # compiles
            best = float("inf")
            for _ in range(args.steps):
                start = time.perf_counter()
                jax.block_until_ready(fn(q_n, q_r, cache, pos))
                best = min(best, time.perf_counter() - start)
            return best, out

        def base(pos):
            pairs = float(sum(np.asarray(pos, np.int64) * t
                              + t * (t + 1) // 2))
            flops = pairs * heads * 2.0 * (NOPE + ROPE + DV)
            floor = (None if rehearsal else opcount.least_seconds(
                flops, 0.0, opcount.peaks(jax.devices()[0].device_kind))[0])
            return {"shape": shape, "first": int(pos.min()),
                    "last": int(pos.max()), "rows_a_key_head":
                    t * heads // groups, "floor_s": floor}

        scan_fn, scans, done = layer(False), [], len(lines)
        for pos in starts:
            s, out = timed(scan_fn, pos)
            scans.append(out)
            lines.append(dict(base(pos), variant="scan", call_s=s))
        # block x tile [x key heads a grid step]; "rule": prefill_form's
        for form in args.tiles.split(","):
            if form == "rule":
                latent.prefill_form = rule
            else:
                block, tile, each = (int(v) for v in (form + "x1").split(
                    "x")[:3])
                if groups % each:
                    continue
                latent.prefill_form = lambda t_, per, groups_, cap_, f=(
                    block, tile, each): (min(f[0], cap_),
                                         min(f[1], -(-t_ // 8) * 8), f[2])
            fn = layer(True)
            for pos, scan in zip(starts, scans):
                try:
                    s, got = timed(fn, pos)
                except Exception as e:        # what Mosaic refused
                    lines.append(dict(base(pos), variant="kernel " + form,
                                      refused=str(e)[:300]))
                    break
                lines.append(dict(
                    base(pos), variant="kernel " + form, call_s=s,
                    max_abs_diff_from_scan=float(jnp.abs(got - scan).max()),
                    scan_scale=float(jnp.abs(scan).max())))
        latent.prefill_form = rule
        for line in lines[done:]:
            print(json.dumps(line), flush=True)
    return lines


if __name__ == "__main__":
    sys.exit(main())
