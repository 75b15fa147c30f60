"""The paper's experiment entry point: distributed k-core decomposition.

    PYTHONPATH=src python -m repro.launch.kcore_run --graph FC --scale 0.2
    PYTHONPATH=src python -m repro.launch.kcore_run --graph chain --n 2000
    PYTHONPATH=src python -m repro.launch.kcore_run --graph FC --mode block_gs
    PYTHONPATH=src python -m repro.launch.kcore_run --graph FC --fused
    PYTHONPATH=src python -m repro.launch.kcore_run --graph ba --mesh 4 --fused
    PYTHONPATH=src python -m repro.launch.kcore_run --graph ba --fused --dispatch on
    PYTHONPATH=src python -m repro.launch.kcore_run --graph LJ1 --scale 0.01 \
        --out-of-core --mem-budget $((4 << 20))

Prints the paper's measurement set: total messages, messages/active nodes
per round, rounds to convergence, work bound, heartbeat-model overhead, and
the simulated-network runtime — plus validation vs the BZ oracle.

``--fused`` runs the whole round loop as ONE device-resident
``lax.while_loop`` (the shared fused runtime, repro/core/runtime.py) with
bit-equal message accounting; ``--mesh N`` runs the sharded engine on an
N-device ("data",) mesh: N chips on TPU (fewer is an error), N forced host
devices on the CPU platform (the flag must precede the first jax backend
init, so this module defers its jax imports). The two compose: ``--mesh N
--fused`` nests the masked shard_map superstep inside the while_loop.

``build_graph``, ``decompose`` and ``report`` are the steps ``main`` runs
after ``repro.platform.configure_run``, importable so other callers
(chip_smoke.py) take the same path.
"""

from __future__ import annotations

import argparse
import json
import time


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="FC", help="SNAP abbrev (Table I) or chain/ba/er")
    ap.add_argument("--scale", type=float, default=0.2)
    ap.add_argument("--n", type=int, default=1000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mode", default="jacobi", choices=["jacobi", "block_gs"])
    ap.add_argument(
        "--fused",
        action="store_true",
        help="run the round loop as one device-resident while_loop "
        "(jacobi only; accounting bit-equal to the host loop)",
    )
    ap.add_argument(
        "--out-of-core",
        action="store_true",
        help="block-cycling decomposition on bounded device memory "
        "(repro.core.outofcore): arc blocks spill to disk and cycle "
        "through an LRU cache; bills bit-equal to the in-memory modes",
    )
    ap.add_argument(
        "--mem-budget",
        type=int,
        default=None,
        metavar="BYTES",
        help="out-of-core LRU block-cache budget in bytes (drives the "
        "block-count plan; default: 8 blocks, unbounded cache)",
    )
    ap.add_argument(
        "--blocks",
        type=int,
        default=None,
        metavar="N",
        help="force the out-of-core block count instead of planning it "
        "from --mem-budget",
    )
    ap.add_argument(
        "--mesh",
        type=int,
        default=0,
        metavar="N",
        help="run the sharded engine on an N-device ('data',) mesh: N "
        "chips on TPU, N forced host devices on the CPU platform",
    )
    ap.add_argument(
        "--platform",
        default=None,
        choices=["cpu", "tpu"],
        help="select the jax platform (repro.platform.set_platform); the "
        "run fails if jax's first device is elsewhere",
    )
    ap.add_argument(
        "--devices",
        type=int,
        default=0,
        metavar="N",
        help="force N host (CPU) devices (repro.platform; applied before "
        "jax backend init, like REPRO_HOST_DEVICES)",
    )
    ap.add_argument(
        "--dispatch",
        default=None,
        choices=["auto", "pallas", "xla", "on", "off"],
        help="superstep kernel dispatch (repro.core.dispatch): auto routes "
        "to the Pallas kernels on TPU and the XLA segment ops on CPU; "
        "on/pallas forces the kernels (interpreted on CPU), off/xla keeps "
        "the XLA segment ops. Default: the REPRO_PALLAS env var, else auto",
    )
    ap.add_argument("--json", action="store_true")
    ap.add_argument(
        "--trace",
        default=None,
        metavar="OUT.json",
        help="enable span tracing and export a Chrome trace_event JSON "
        "(open in Perfetto / chrome://tracing)",
    )
    ap.add_argument(
        "--metrics",
        action="store_true",
        help="dump the process metrics registry after the run "
        "(see --metrics-format / --metrics-out)",
    )
    ap.add_argument(
        "--metrics-format",
        default="json",
        choices=["json", "prom"],
        help="stdout format for --metrics: structured JSON (default) or "
        "the Prometheus text exposition format",
    )
    ap.add_argument(
        "--metrics-out",
        default=None,
        metavar="PATH",
        help="also write the metrics registry to a file (implies "
        "--metrics); format inferred from the extension: .prom/.txt -> "
        "Prometheus text, anything else -> JSON",
    )
    ap.add_argument(
        "--flight",
        default=None,
        metavar="OUT.json",
        help="enable the convergence flight recorder + invariant monitor "
        "and dump the per-round ring and health verdict as JSON",
    )
    args = ap.parse_args(argv)
    if args.metrics_out:
        args.metrics = True
    if args.mesh and args.mode != "jacobi":
        # the sharded engine is jacobi only; refuse rather than silently
        # running (and reporting) a different mode than asked
        ap.error("--mesh supports --mode jacobi only")
    if args.out_of_core and (args.mesh or args.fused or args.mode != "jacobi"):
        ap.error("--out-of-core is its own engine: jacobi only, "
                 "no --mesh/--fused")
    if (args.mem_budget or args.blocks) and not args.out_of_core:
        ap.error("--mem-budget/--blocks require --out-of-core")
    return args


def build_graph(args, generators):
    if args.graph == "chain":
        return generators.chain(args.n)
    if args.graph == "ba":
        return generators.barabasi_albert(args.n, 4, seed=args.seed)
    if args.graph == "er":
        return generators.erdos_renyi(args.n, 4 * args.n, seed=args.seed)
    return generators.snap_analogue(args.graph, scale=args.scale, seed=args.seed)


def decompose(args, g):
    """Run the engine ``args`` selects on ``g``; returns (result, wall_s)."""
    from repro.core import KCoreConfig, kcore_decompose, kcore_decompose_sharded

    t0 = time.perf_counter()
    if args.out_of_core:
        from repro.core.outofcore import outofcore_decompose

        res = outofcore_decompose(g, mem_budget=args.mem_budget, n_blocks=args.blocks)
    elif args.mesh:
        from repro.distribution.compat import make_mesh

        mesh = make_mesh((args.mesh,), ("data",))
        res = kcore_decompose_sharded(g, mesh, ("data",), fused=args.fused)
    else:
        config = KCoreConfig(mode=args.mode)
        res = kcore_decompose(g, config, fused=args.fused)
    return res, time.perf_counter() - t0


def report(args, g, res, wall: float, oracle) -> dict:
    """The paper's measurement set for one run, checked against the BZ
    core numbers ``oracle``."""
    from repro.core import work_bound
    from repro.core.cost_model import DATACENTER, INTERNET, TPU_POD, simulate_runtime
    from repro.core.messages import heartbeat_overhead

    ok = bool((res.core == oracle).all())
    wb = work_bound(g, res.core)
    hb = heartbeat_overhead(res.stats)
    out = {
        "graph": args.graph,
        "n": g.n,
        "m": g.m,
        "avg_deg": round(g.avg_deg, 1),
        "max_deg": g.max_deg,
        "max_core": int(res.core.max()) if g.n else 0,
        "mode": args.mode,
        "fused": args.fused,
        "dispatch": res.dispatch,
        "mesh": args.mesh or 1,
        "correct_vs_BZ": ok,
        "rounds": res.rounds,
        "converged": res.converged,
        "total_messages": res.stats.total_messages,
        "work_bound": wb,
        "messages_over_bound": round(res.stats.total_messages / max(wb, 1), 3),
        "messages_per_round": res.stats.messages_per_round.tolist()[:20],
        "active_per_round": res.stats.active_per_round.tolist()[:20],
        "heartbeats": hb["heartbeat_messages"],
        "wall_s": round(wall, 2),
        "recompiles": res.recompiles,
        "compile_s": round(res.compile_s, 3),
        "phase_s": {k: round(v, 4) for k, v in res.phase_s.items()},
        "simulated_runtime_s": {
            m.name: round(simulate_runtime(res.stats, m)["total_s"], 4)
            for m in (INTERNET, DATACENTER, TPU_POD)
        },
    }
    if args.out_of_core and res.block_stats is not None:
        out["out_of_core"] = res.block_stats.to_json()
    return out


def main() -> None:
    args = parse_args()
    from repro import platform

    platform.configure_run(
        platform=args.platform, devices=args.devices, dispatch=args.dispatch, mesh=args.mesh
    )

    from repro.core import bz_core_numbers
    from repro.graph import generators
    from repro.obs import metrics, trace

    if args.trace:
        trace.enable()
    if args.flight:
        from repro.obs import flight, health

        flight.enable()
        health.install()

    g = build_graph(args, generators)
    res, wall = decompose(args, g)
    rep = report(args, g, res, wall, bz_core_numbers(g))
    if args.json:
        print(json.dumps(rep, indent=1))
    else:
        for k, v in rep.items():
            print(f"{k}: {v}")
    if args.trace:
        trace.export(args.trace)
        print(f"trace: {args.trace} ({len(trace.events())} events)")
    if args.metrics:
        # fold the run's headline numbers into the process registry so the
        # dump is useful even for a single static decomposition
        labels = {"graph": args.graph}
        metrics.counter("kcore_rounds_total", **labels).inc(res.rounds)
        metrics.counter("kcore_messages_total", **labels).inc(int(res.stats.total_messages))
        metrics.gauge("kcore_compile_seconds", **labels).set(res.compile_s)
        metrics.gauge("kcore_wall_seconds", **labels).set(wall)
        for phase, secs in res.phase_s.items():
            metrics.gauge("kcore_phase_seconds", graph=args.graph, phase=phase).set(secs)
        if args.metrics_format == "prom":
            print(metrics.to_prometheus(), end="")
        else:
            print(json.dumps({"metrics": metrics.to_json()}, indent=1))
        if args.metrics_out:
            prom_file = args.metrics_out.endswith((".prom", ".txt"))
            with open(args.metrics_out, "w") as f:
                if prom_file:
                    f.write(metrics.to_prometheus())
                else:
                    json.dump({"metrics": metrics.to_json()}, f, indent=1)
            print(f"metrics: {args.metrics_out} ({'prom' if prom_file else 'json'})")
    if args.flight:
        from repro.obs import flight, health

        payload = flight.to_json()
        payload["health"] = health.verdict()
        with open(args.flight, "w") as f:
            json.dump(payload, f)
        print(
            f"flight: {args.flight} (runs={payload['runs']} "
            f"rounds={payload['rounds_recorded']} health={payload['health']['status']})"
        )
    if not rep["correct_vs_BZ"]:
        raise SystemExit("core numbers disagree with BZ oracle!")


if __name__ == "__main__":
    main()
