"""AOT capacity proof for the segmented bsp kernel at 10x-Reddit scale.

VERDICT r3 item 3: the module's stated regime ("V ~ 10x Reddit and up",
ops/bsp_ell.py) needed ~1.4-1.75M blocks while the packed SMEM key
capped at ~250k. The fix is grid segmentation (BspEll.build): every
pallas_call carries at most NTS_BSP_MAX_BLOCKS blocks, covering one
contiguous dst-tile range, with segment-LOCAL keys — the compiled
program is independent of V; only the Python-level segment count grows.

Provability: when a build segments (n_seg > 1) it QUANTIZES the program
shape — b_seg snaps to the exact 8-value menu ``bsp_bseg_menu(cap)``
(seven quantum steps + the cap) and t_seg (the per-call output tile
count) snaps to the <=16-value menu ``bsp_tseg_menu(t_dst)`` — so every
segmented program at any scale comes from the finite
(b_seg menu) x (t_seg menu) lattice, which this tool compiles IN FULL
(~100 programs at ~1.7 s each; ADVICE r4 flagged the previous
3-candidate t_seg band for missing the values real builds emit). The
per-BLOCK geometry (the Mosaic lowering surface: [1,K,R] tables, the
[vt,f] slab, the [dt,f] output tile, the W one-hot build) is
t_seg-invariant; t_seg only sizes the output HBM buffer and the index
map range, which is why the whole lattice compiles in minutes with no
chip claimed. Green across the lattice means every segmented program
the builder can emit at that scale is pre-lowered into the persistent
compile cache — no first-run full-scale Mosaic compile on chip.

Reference analog: the beyond-shared-mem tiled CUDA aggregation
(cuda/ntsCUDAFuseKernel.cuh:163-207) whose shared-memory tile also had
to be proven at the target scale.

Usage: python -m neutronstarlite_tpu.tools.aot_bsp_scale
         [--scale 10.0] [--topology v5e:2x2] [--f 602]
Prints ONE JSON line: {ok, scale, b_seg, t_src, programs: [{t_seg,
compile_s, *_gib}], smem_key_kib | error}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

REDDIT_V = 232_965  # BASELINE.md north-star vertex count


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=10.0)
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--f", type=int, default=602)
    ap.add_argument(
        "--dist", type=int, default=0, metavar="P",
        help="compile the lattice at the DIST per-shard RECTANGULAR "
        "geometry instead of the single-chip square one: dst space is one "
        "shard's vp = roundup8(ceil(V/P)) rows, src space is the full "
        "all_gathered P*vp slab (parallel/dist_bsp.py) — VERDICT r4 item 6's "
        "'dist-bsp at 10x-Reddit AOT-green' without synthesizing a "
        "1.15B-edge graph (the kernel program depends only on geometry)",
    )
    args = ap.parse_args(argv)

    # contract: no accelerator claimed — CPU host, topology compiler only
    import jax

    jax.config.update("jax_platforms", "cpu")
    os.environ["NTS_PALLAS_FORCE_COMPILED"] = "1"
    from neutronstarlite_tpu.utils.platform import start_runtime

    start_runtime()

    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

    from neutronstarlite_tpu.ops.bsp_ell import (
        DEFAULT_DT,
        DEFAULT_K,
        DEFAULT_MAX_BLOCKS,
        DEFAULT_R,
        DEFAULT_VT,
        _bsp_call,
        bsp_bseg_menu,
        bsp_tseg_menu,
    )

    v_num = int(REDDIT_V * args.scale)
    dt, vt, K, R = DEFAULT_DT, DEFAULT_VT, DEFAULT_K, DEFAULT_R
    cap = int(os.environ.get("NTS_BSP_MAX_BLOCKS", DEFAULT_MAX_BLOCKS))
    if args.dist > 0:
        # per-shard rectangular geometry (parallel/dist_bsp.py): dst rows
        # are one shard's padded vp, the src space is the all_gathered
        # [P*vp] slab. vp must be EXACT (r5 review: the degree-balanced
        # partition_offsets max span exceeds ceil(V/P) — a 2.4%-off vp
        # shifts t_dst/t_src and every compiled program shape), so it is
        # computed from the real generator's degree vector via the real
        # partitioner — the one-shot edge draw is minutes at 10x, cheap
        # next to a wrong cache seed.
        import numpy as _np

        from neutronstarlite_tpu.graph.storage import partition_offsets
        from neutronstarlite_tpu.graph.synthetic import (
            synthetic_power_law_graph,
        )
        from neutronstarlite_tpu.parallel.vertex_space import round_up

        P = args.dist
        e_num = max(int(114_615_892 * args.scale), 512)
        src_a, dst_a = synthetic_power_law_graph(v_num, e_num, seed=7)
        del src_a
        in_deg = _np.bincount(dst_a, minlength=v_num).astype(_np.int64)
        del dst_a
        offs = partition_offsets(v_num, in_deg, P)
        vp = round_up(max(int(_np.diff(offs).max()), 1), 8)
        t_dst = -(-vp // dt)
        t_src = -(-(P * vp) // vt)
    else:
        t_dst = -(-v_num // dt)
        t_src = -(-v_num // vt)
    cap_eff = (cap // 8) * 8
    bseg_menu = bsp_bseg_menu(cap_eff)
    # t_seg menu: the builder snaps every segmented t_seg UP to
    # bsp_tseg_menu(t_dst) (ADVICE r4: the old 3-candidate band missed
    # the roundup128(tiles) values real builds emit, e.g. ~640-768 at
    # 10x Reddit), so compiling the full menu here makes every
    # emittable program literally pre-lowered.
    # + exact t_dst, the call shape of the unsegmented fast path. Scope
    # (r5 review): this lattice covers every SEGMENTED program exactly
    # (segmented b_seg/t_seg are menu-snapped); an UNSEGMENTED program's
    # block count is roundup8(data blocks) — data-dependent, not
    # menu-aligned — so its exact (b, t_dst) pair is seeded by
    # tools/aot_bench_path (which builds the real tables for each bench
    # leg), not by this geometry-only tool. Unsegmented programs only
    # arise under the SMEM cap, where Mosaic compiles have never hung.
    cands = sorted(set(bsp_tseg_menu(t_dst)) | {t_dst})
    out = {
        "scale": args.scale, "v_num": v_num, "topology": args.topology,
        "dist_partitions": args.dist or None,
        "bseg_menu": bseg_menu, "t_src": t_src, "t_dst": t_dst,
        "f": args.f,
        "smem_key_kib_max": round(bseg_menu[-1] * 4 / 1024, 1),
        "programs": [],
    }
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name=args.topology
        )
        mesh1 = Mesh(np.array(list(topo.devices)[:1]), ("one",))
        rep = NamedSharding(mesh1, PS())

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

        import jax.numpy as jnp

        # slab dtype is part of the program: the bench's production slab
        # is bf16; the dist exchange's default (f-chunked standard order)
        # feeds f32 — dist mode compiles both
        slab_dtypes = (
            (jnp.bfloat16, jnp.float32) if args.dist else (jnp.bfloat16,)
        )

        def call_width(t_call: int) -> int:
            """The EXACT per-call slab width DistBsp._local_aggregate
            feeds at this geometry — THE SAME function the runtime calls
            (dist_bsp.bsp_call_width), so the tool cannot drift."""
            if not args.dist:
                return args.f
            from neutronstarlite_tpu.parallel.dist_bsp import bsp_call_width

            return bsp_call_width(t_call, dt, args.f)

        for b_seg in bseg_menu:
            for slab_dt in slab_dtypes:
                shapes = (
                    sds((b_seg,), jnp.int32),            # blk_key
                    sds((b_seg, K, R), jnp.int32),       # nbr
                    sds((b_seg, K, R), jnp.float32),     # wgt
                    sds((b_seg, R), jnp.int32),          # ldst
                )
                for t_seg in cands:
                    f_call = call_width(t_seg)
                    shapes = shapes[:4] + (
                        sds((t_src * vt, f_call), slab_dt),  # xp slab
                    )
                    t0 = time.time()
                    compiled = _bsp_call.lower(
                        *shapes, dt=dt, vt=vt, t_dst=t_seg, t_src=t_src,
                        interpret=False,
                    ).compile()
                    mem = compiled.memory_analysis()
                    out["programs"].append({
                        "b_seg": b_seg,
                        "t_seg": t_seg,
                        "f": f_call,
                        "slab": jnp.dtype(slab_dt).name,
                        "compile_s": round(time.time() - t0, 1),
                        "argument_gib": round(
                            mem.argument_size_in_bytes / 2**30, 3
                        ),
                        "temp_gib": round(mem.temp_size_in_bytes / 2**30, 3),
                        "output_gib": round(
                            mem.output_size_in_bytes / 2**30, 3
                        ),
                    })
        out["ok"] = True
    except Exception as e:  # noqa: BLE001 — report, don't trace-dump
        out.update(ok=False, error=f"{type(e).__name__}: {str(e)[:500]}")
    print(json.dumps(out))
    return 0 if out.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
