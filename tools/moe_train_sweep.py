#!/usr/bin/env python3
"""Time the trained expert layer's held experts on the attached chip: the
row scatter and gather alone (XLA's forms beside ``add_rows``), the grouped
products alone over their tiles, and the whole layer forward and backward
over the sorted pairs a loop turn takes — the sweep that ``ops/moe.py``'s
``TRAIN_PAIR_ROWS``, ``TRAIN_TILES`` and ``TRAIN_OUTER_TILES`` hold the
result of (PERF.md section 6).

    python tools/moe_train_sweep.py [--tokens 8192] [--held-share 0.27]
        [--rows 1024,2048,4096] [--parts rows,products,layer]
        [--out chiprun_out/moe_train_sweep.json]

One layer at the cell's widths (2048 x 1536, 8 of 64 experts held, 4 a
token), the routing drawn so that ``--held-share`` of the pairs fall to held
experts (0.125 at the first step, 0.27 over a window: PERF.md). Each
measurement is ``--chain`` calls dispatched back to back, best of
``--repeats``; a tiling the compiler refuses is a row with its error. Needs
a TPU; prints one JSON line a measurement. (``SWEEP_ANYWAY=1`` runs the
script off the chip, interpreted at widths of 128: a dry run of the script,
not a measurement.)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _best_ms(fn, args, chain: int, repeats: int) -> float:
    import jax

    jax.block_until_ready(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = None
        for _ in range(chain):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return 1e3 * best / chain


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--tokens", type=int, default=8192)
    parser.add_argument("--held-share", type=float, default=0.27)
    parser.add_argument("--rows", default="1024,2048,4096")
    parser.add_argument("--tilings", default="256x2048x1536,512x2048x1536,"
                        "1024x2048x1536,512x512x1536,512x2048x768,"
                        "512x512x512",
                        help="most rows x contraction x columns a grid step")
    parser.add_argument("--outer", default="512x2048x768,1024x2048x768,"
                        "512x1024x768,256x2048x768,512x512x512")
    parser.add_argument("--parts", default="rows,products,layer")
    parser.add_argument("--chain", type=int, default=8)
    parser.add_argument("--repeats", type=int, default=4)
    parser.add_argument("--out", default="chiprun_out/moe_train_sweep.json")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from determined_clone_tpu.ops import grouped_matmul as gm
    from determined_clone_tpu.ops import moe

    if jax.default_backend() != "tpu" and not os.environ.get("SWEEP_ANYWAY"):
        print("moe_train_sweep.py times a chip; none is attached",
              file=sys.stderr)
        return 2

    N, D, F, H, E, K = args.tokens, 2048, 1536, 8, 64, 4
    if os.environ.get("SWEEP_ANYWAY"):
        D, F = 128, 128
    bf16, f32 = jnp.bfloat16, jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    # held experts weigh more, so that about held_share of the pairs are held
    p = args.held_share
    logits = jnp.where(jnp.arange(E) < H, jnp.log(p / H),
                       jnp.log((1 - p) / (E - H)))
    noise = jax.random.gumbel(keys[0], (N, E))
    _, experts = jax.lax.top_k(logits[None, :] + noise, K)
    experts = experts.astype(jnp.int32)
    gates = jax.random.uniform(keys[1], (N, K), f32, 0.2, 0.7)
    h = jax.random.normal(keys[2], (N, D), f32)
    dy = jax.random.normal(keys[3], (N, D), f32) * 0.01
    wg = jax.random.normal(keys[4], (H, D, F), f32) * 0.02
    wu = jax.random.normal(keys[5], (H, D, F), f32) * 0.02
    wd = jax.random.normal(keys[6], (H, F, D), f32) * 0.02
    plan = moe._pair_plan(experts, gates, first_expert=0, n_held=H, tile=256)
    held = int(plan.counts[0])
    rows_out = []

    def note(**row):
        rows_out.append(row)
        print(json.dumps(row), flush=True)

    note(what="routing", tokens=N, pairs=N * K, held=held,
         sizes=[int(s) for s in plan.sizes])
    parts = args.parts.split(",")
    tilings = [tuple(int(v) for v in t.split("x"))
               for t in args.tilings.split(",")]
    outers = [tuple(int(v) for v in t.split("x"))
              for t in args.outer.split(",")]

    def timed(what, fn, fn_args, **more):
        try:
            ms = _best_ms(jax.jit(fn), fn_args, args.chain, args.repeats)
            note(what=what, ms=round(ms, 4), **more)
        except Exception as e:  # a tiling the compiler refuses is a result
            note(what=what, error=f"{type(e).__name__}: {str(e)[:300]}",
                 **more)

    if "rows" in parts:
        R = -(-held // 512) * 512
        at = jnp.arange(R)
        real = at < held
        token = jnp.where(real, plan.tokens[jnp.where(real, at, 0)], N)
        out = jax.random.normal(keys[7], (R, D), f32)
        x16 = h.astype(bf16)

        def tiles(flags):
            def run(token, out):
                def one(t, y):
                    idx = jax.lax.dynamic_slice_in_dim(token, t * 256, 256)
                    upd = jax.lax.dynamic_slice_in_dim(out, t * 256, 256)
                    if flags:
                        idx = jnp.where(idx < N, idx, N + jnp.arange(256))
                    return y.at[idx].add(upd, mode="drop",
                                         indices_are_sorted=flags,
                                         unique_indices=flags)
                return jax.lax.fori_loop(0, R // 256, one,
                                         jnp.zeros((N, D), f32))
            return run

        timed("scatter_add.tiles_of_256", tiles(False), (token, out), rows=R)
        timed("scatter_add.tiles_of_256.sorted_unique", tiles(True),
              (token, out), rows=R)
        timed("scatter_add.once", lambda t, o: jnp.zeros((N, D), f32).at[
            t].add(o, mode="drop"), (token, out), rows=R)
        by_token = jnp.argsort(token)
        timed("scatter_add.once.sorted", lambda t, o: jnp.zeros(
            (N, D), f32).at[t].add(o, mode="drop", indices_are_sorted=True),
            (token[by_token], out[by_token]), rows=R)
        timed("segment_sum.sorted", lambda t, o: jax.ops.segment_sum(
            o, t, num_segments=N + 1, indices_are_sorted=True),
            (token[by_token], out[by_token]), rows=R)
        timed("gather.bf16.once", lambda t, x: x[jnp.minimum(t, N - 1)],
              (token, x16), rows=R)
        timed("gather.f32.once", lambda t, x: x[jnp.minimum(t, N - 1)],
              (token, out[:N] if R >= N else h), rows=R)
        # the other way round: every token fetches its k pairs' rows (a row
        # of zeros where a pair is not held), work in proportion to N k
        place = jnp.full((N * K,), R, jnp.int32).at[
            jnp.where(real, plan.order[jnp.where(real, at, 0)], N * K)
        ].set(at.astype(jnp.int32), mode="drop").reshape(N, K)
        timed("combine.gather_every_pair", lambda o, pl_: jnp.sum(
            jnp.concatenate([o, jnp.zeros((1, D), f32)])[pl_], axis=1),
            (out, place), rows=N * K)
        in_tiles = lambda a: a.reshape(a.shape[0], -1, 128)  # noqa: E731
        for step in (128, 256, 512):
            timed("add_rows.kernel", lambda t, o, s_, step=step: gm.add_rows(
                in_tiles(jnp.zeros((N, D), f32)), t, in_tiles(o), s_,
                rows_a_step=step), (token, out, plan.sizes), rows=R,
                rows_a_step=step)
        timed("permute.f32.once", lambda o, p: o[p], (out, by_token), rows=R)

    if "products" in parts:
        m = 8192
        sizes = jnp.full((H,), m // H, jnp.int32)
        xs = jax.random.normal(keys[7], (m, D), f32).astype(bf16)
        acts = jax.random.normal(keys[7], (m, F), f32).astype(bf16)
        w16, wd16 = wg.astype(bf16), wd.astype(bf16)
        for tiling in tilings:
            name = "x".join(str(v) for v in tiling)
            timed("gmm.up", lambda a, b, s, t=tiling: gm.grouped_matmul(
                a, b, s, tiles=t),
                  (xs, w16, sizes), tiling=name, peak_ms=round(
                      2e3 * m * D * F / 197e12, 4))
            timed("gmm.down", lambda a, b, s, t=tiling: gm.grouped_matmul(
                a, b, s, tiles=t),
                  (acts, wd16, sizes), tiling=name)
            timed("gmm.up.transposed", lambda a, b, s, t=tiling:
                  gm.grouped_matmul(a, b, s, transpose=True, tiles=t),
                  (acts, w16, sizes),
                tiling=name)
        for tiling in outers:
            name = "x".join(str(v) for v in tiling)
            timed("outer.up", lambda a, b, s, z, t=tiling: gm.grouped_outer(
                a.T, b, s, z, tiles=t),
                (xs, acts, sizes, jnp.zeros((H, D, F), f32)), tiling=name)
            timed("outer.down", lambda a, b, s, z, t=tiling: gm.grouped_outer(
                a.T, b, s, z, tiles=t),
                (acts, xs, sizes, jnp.zeros((H, F, D), f32)), tiling=name)
        timed("ragged_dot.up", lambda a, b, s: jax.lax.ragged_dot(
            a, b, s, preferred_element_type=f32), (xs, w16, sizes))

    if "layer" in parts:
        def both(layer):
            # the gradient alone: nothing of it needs the forward's sums,
            # and the compiler drops them (as it does under remat)
            return jax.grad(lambda h, gates, wg, wu, wd, dy: jnp.sum(
                layer(h, gates, wg, wu, wd) * dy), argnums=(0, 1, 2, 3, 4))

        for rows in (int(v) for v in args.rows.split(",")):
            for tiling in tilings[:int(os.environ.get("LAYER_TILINGS", 2))]:
                def layer(h, g, a, b, c, rows=rows, tiling=tiling):
                    moe.TRAIN_TILES = tiling  # read as the layer is traced
                    return moe._held_experts(h, g, experts, a, b, c, 0, rows,
                                             bf16)
                name = "x".join(str(v) for v in tiling)
                timed("layer.forward", layer, (h, gates, wg, wu, wd),
                      rows=rows, tiling=name, held=held)
                timed("layer.backward", both(layer),
                      (h, gates, wg, wu, wd, dy), rows=rows, tiling=name,
                      held=held)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows_out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
