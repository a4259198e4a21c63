"""Single-chip kernel micro-benchmarks (docs/PERF.md §1, reproducible).

Round 2's §1 table came from ad-hoc scripts; this tool makes the method
durable and extends it to the round-3 kernels. Shapes follow §1: the
5%-Reddit edge set (E=5.73M) over the half-Reddit vertex table
([116k, f]), bf16 compute. Timing defeats the remote execution path's
identical-dispatch caching by feeding a fresh scalar into every
iteration (naive repeat-timing reports impossible numbers — §1's note);
reported time is the median of ``--iters`` post-compile runs.

Ops: dense matmul / HBM stream (method validation against hardware
peaks), random row gather, XLA ELL aggregate, sorted scatter-add, fused
Pallas ELL (VMEM-resident), fused Pallas ELL at 602 wide (the round-3
feature-column-chunked regime), and the streamed block-sparse kernel
(ops/bsp_ell.py). Failures (e.g. a Mosaic lowering gap) are recorded
per-op, never fatal.

Usage: python -m neutronstarlite_tpu.tools.micro_bench [--iters 10]
Prints ONE JSON line; the recovery plan step ``micro_kernels`` archives
it under docs/perf_runs/round3/.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

V = 116482  # half Reddit (the §1 table shapes)
E = 5730794  # 5% Reddit edges
F = 128
F_WIDE = 602


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument(
        "--scale", type=float, default=1.0,
        help="shrink V/E (CPU smoke tests; 1.0 = the §1 table shapes)",
    )
    ap.add_argument(
        "--ops", default="",
        help="comma-separated op-name substrings to run (default: all). "
        "A hung Mosaic compile stalls this process in C++ where no Python "
        "timeout can interrupt it — run suspect ops as separate invocations "
        "(the recovery plan's per-step subprocess timeout is the kill)",
    )
    args = ap.parse_args(argv)
    op_filter = [s for s in args.ops.split(",") if s]
    global V, E
    V = max(int(V * args.scale), 64)
    E = max(int(E * args.scale), 512)

    from neutronstarlite_tpu.utils.platform import start_runtime

    start_runtime()
    import jax
    import jax.numpy as jnp

    from neutronstarlite_tpu.graph.storage import build_graph
    from neutronstarlite_tpu.graph.synthetic import synthetic_power_law_graph
    from neutronstarlite_tpu.ops.bsp_ell import BspEllPair, bsp_gather_dst_from_src
    from neutronstarlite_tpu.ops.device_graph import DeviceGraph
    from neutronstarlite_tpu.ops.aggregate import gather_dst_from_src
    from neutronstarlite_tpu.ops.edge import (
        aggregate_edge_to_dst_weighted,
        edge_softmax,
    )
    from neutronstarlite_tpu.ops.ell import EllPair, ell_gather_dst_from_src
    from neutronstarlite_tpu.ops.fused_edge import (
        FusedEdgePair,
        fused_edge_attention_aggregate,
    )

    def key_rng(key: str) -> np.random.Generator:
        # one independent stream per builder key: array contents must not
        # depend on demand order (an --ops-filtered triage run and a full
        # run build resources in different orders; a shared stream would
        # make them measure different random data)
        import zlib

        return np.random.default_rng([args.seed, zlib.crc32(key.encode())])

    out = {"platform": jax.default_backend(), "device": str(jax.devices()[0]),
           "V": V, "E": E, "ops": {}}

    def selected(name: str) -> bool:
        return not op_filter or any(s in name for s in op_filter)

    # Every input — graph tables AND dense arrays — is built lazily through
    # this cache, so a filtered triage run pays only for what its ops touch
    # (the bsp packing and the 233k x 602 wide table are minutes/hundreds
    # of MB at --scale 2.0 on the 1-core rig). Ops declare their resources
    # by key in OPS below; there is exactly one place op names live.
    built = {}

    def need(key):
        if key not in built:
            print(f"building {key} (host)...", file=sys.stderr, flush=True)
            built[key] = builders[key]()
        return built[key]

    builders = {
        "g": lambda: build_graph(
            *synthetic_power_law_graph(V, E, seed=args.seed), V,
            weight="gcn_norm",
        ),
        "dg": lambda: DeviceGraph.from_host(need("g")),
        "ell": lambda: EllPair.from_host(need("g")),
        "bsp": lambda: BspEllPair.from_host(need("g"), dt=512, vt=8192),
        "x": lambda: jnp.asarray(
            key_rng("x").standard_normal((V, F)).astype(np.float32),
            jnp.bfloat16,
        ),
        "xw": lambda: jnp.asarray(
            key_rng("xw").standard_normal((V, F_WIDE)).astype(np.float32),
            jnp.bfloat16,
        ),
        "w_mm": lambda: jnp.asarray(
            key_rng("w_mm").standard_normal((F_WIDE, F)).astype(np.float32),
            jnp.bfloat16,
        ),
        "idx": lambda: jnp.asarray(
            key_rng("idx").integers(0, V, size=E), jnp.int32
        ),
        "big": lambda: jnp.asarray(
            key_rng("big").standard_normal(8 << 20).astype(np.float32)  # 32 MB
        ),
        # ---- edge family (GAT/GGCN attention chains): unit-weight graph,
        # the eager DeviceGraph chain vs the fused blocked kernel
        "g1": lambda: build_graph(
            *synthetic_power_law_graph(V, E, seed=args.seed), V,
            weight="ones",
        ),
        "dg1": lambda: DeviceGraph.from_host(need("g1")),
        "fused": lambda: FusedEdgePair.from_host(need("g1")),
        "al": lambda: jnp.asarray(
            key_rng("al").standard_normal((V, 1)).astype(np.float32)
        ),
        "ar": lambda: jnp.asarray(
            key_rng("ar").standard_normal((V, 1)).astype(np.float32)
        ),
        "hs": lambda: jnp.asarray(
            key_rng("hs").standard_normal((V, F)).astype(np.float32),
            jnp.bfloat16,
        ),
        "hd": lambda: jnp.asarray(
            key_rng("hd").standard_normal((V, F)).astype(np.float32),
            jnp.bfloat16,
        ),
    }

    def eager_edge_chain(dg, h, a_src, a_dst, slope):
        """The decoupled score -> per-dst softmax -> weighted-aggregate
        chain over the [Ep]-shaped edge space (models/gat.py / ggcn.py)."""
        score = jax.nn.leaky_relu(
            a_src[dg.csc_src] + a_dst[dg.csc_dst], negative_slope=slope
        )
        s = edge_softmax(dg, score)
        return aggregate_edge_to_dst_weighted(dg, s, h)


    def timed(name, make_fn, traffic_bytes=None, flops=None):
        """make_fn() -> fn(scalar) -> array; records median ms (+ rate)."""
        try:
            fn = make_fn()
            jfn = jax.jit(fn)
            jax.block_until_ready(jfn(jnp.float32(1.0)))  # compile
            ts = []
            for i in range(args.iters):
                s = jnp.float32(1.0 + 1e-6 * (i + 1))  # fresh dispatch
                t0 = time.perf_counter()
                jax.block_until_ready(jfn(s))
                ts.append(time.perf_counter() - t0)
            med = float(np.median(ts))
            rec = {"ms": round(med * 1e3, 4)}
            if traffic_bytes:
                rec["apparent_gbs"] = round(traffic_bytes / med / 1e9, 1)
            if flops:
                rec["tflops"] = round(flops / med / 1e12, 1)
            out["ops"][name] = rec
            print(f"{name}: {rec}", file=sys.stderr, flush=True)
        except Exception as e:  # noqa: BLE001 — record, keep going
            out["ops"][name] = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
            print(f"{name} FAILED: {out['ops'][name]}", file=sys.stderr, flush=True)

    # the single source of op names: (name, needs, fn_factory, kwargs).
    # Resources are resolved EAGERLY (outside any jit trace — building a
    # table mid-trace caches leaked tracers) and only for selected ops, so
    # the filter decides what gets built and a rename cannot drift out of
    # sync with a gate
    OPS = [
        ("matmul_bf16_602x128", ("xw", "w_mm"),
         lambda xw, w_mm: lambda s: (xw * s) @ w_mm,
         dict(flops=2.0 * V * F_WIDE * F)),
        ("hbm_stream_f32_64MB", ("big",),
         lambda big: lambda s: big * s,
         dict(traffic_bytes=2 * (8 << 20) * 4)),
        ("row_gather_bf16", ("x", "idx"),
         lambda x, idx: lambda s: (x * s)[idx],
         dict(traffic_bytes=E * F * 2)),
        ("ell_aggregate_xla_bf16", ("ell", "x"),
         lambda ell, x: lambda s: ell_gather_dst_from_src(ell, x * s),
         dict(traffic_bytes=E * F * 2)),
        ("sorted_scatter_bf16", ("dg", "x"),
         lambda dg, x: lambda s: gather_dst_from_src(dg, x * s),
         dict(traffic_bytes=E * F * 2)),
        ("bsp_streamed_bf16", ("bsp", "x"),
         lambda bsp, x: lambda s: bsp_gather_dst_from_src(bsp, x * s),
         dict(traffic_bytes=E * F * 2)),
        # edge family: eager chain vs the fused blocked kernel, fwd+bwd
        # (the fused backward is three streamed passes; forward-only
        # timing would hide most of its cost). The `_eager` / `_fused`
        # suffix pair is what metrics_report --diff canonicalizes when a
        # micro_bench JSON is used as a diff side (scripts/ci_tier1.sh).
        ("edge_gat_eager", ("dg1", "x", "al", "ar"),
         lambda dg, x, al, ar: lambda s: jax.grad(
             lambda h: (eager_edge_chain(dg, h, al, ar, 0.01) ** 2).sum()
         )(x * s),
         dict(traffic_bytes=3 * E * F * 2)),
        ("edge_gat_fused", ("fused", "x", "al", "ar"),
         lambda fe, x, al, ar: lambda s: jax.grad(
             lambda h: (
                 fused_edge_attention_aggregate(fe, h, al, ar, 0.01) ** 2
             ).sum()
         )(x * s),
         dict(traffic_bytes=3 * E * F * 2)),
        ("edge_ggcn_eager", ("dg1", "x", "hs", "hd"),
         lambda dg, x, hs, hd: lambda s: jax.grad(
             lambda h: (eager_edge_chain(dg, h, hs, hd, 0.2) ** 2).sum()
         )(x * s),
         dict(traffic_bytes=3 * E * F * 2)),
        ("edge_ggcn_fused", ("fused", "x", "hs", "hd"),
         lambda fe, x, hs, hd: lambda s: jax.grad(
             lambda h: (
                 fused_edge_attention_aggregate(fe, h, hs, hd, 0.2) ** 2
             ).sum()
         )(x * s),
         dict(traffic_bytes=3 * E * F * 2)),
    ]

    run = [op for op in OPS if selected(op[0])]
    if not run:
        # a filter matching nothing must fail LOUDLY: a vacuous {} with
        # rc 0 would let the supervisor mark a triage step collected
        print(
            f"FATAL: --ops {args.ops!r} matches none of "
            f"{[op[0] for op in OPS]}",
            file=sys.stderr, flush=True,
        )
        return 2
    for name, needs, fn_factory, kwargs in run:
        timed(name,
              lambda ff=fn_factory, nd=needs: ff(*[need(k) for k in nd]),
              **kwargs)

    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
