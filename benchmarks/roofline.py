"""Roofline report: aggregates experiments/dryrun/*.json into the
EXPERIMENTS.md §Roofline table (one row per arch x shape x mesh) with the
three terms, dominant bottleneck, MODEL_FLOPS and the useful-compute ratio.

The dry-run must have been executed first:
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh pod1

Superstep mode (``--superstep``) measures the k-core masked superstep
ITSELF instead of aggregating dry-runs: for each (graph, dispatch) pair it
compiles the dispatched round program (repro.core.dispatch), reads the
compiled cost analysis (flops / bytes accessed), times the superstep wall,
and reports achieved vs peak flops/s and bytes/s against the platform
layer's per-backend peaks (repro.platform.peaks) — the measurable
trajectory toward the EEN-118/FC-283 ms/round floor:

    PYTHONPATH=src python -m benchmarks.roofline --superstep --json out.json
"""

from __future__ import annotations

import json
import pathlib
import time

from repro.configs import get_config
from repro.configs.registry import shape_by_name

DRYRUN_DIR = pathlib.Path(__file__).resolve().parents[1] / "experiments" / \
    "dryrun"


def model_flops(arch: str, shape_name: str) -> float:
    """MODEL_FLOPS: 6·N·D (dense train) / 6·N_active·D (MoE train);
    2·N_active per decoded token; prefill = 2·N_active·D."""
    cfg = get_config(arch)
    shape = shape_by_name(arch, shape_name)
    if cfg.family == "lm":
        n_act = cfg.n_active_params
        if shape.kind == "train":
            D = shape.params["seq_len"] * shape.params["global_batch"]
            return 6.0 * n_act * D
        if shape.kind == "prefill":
            D = shape.params["seq_len"] * shape.params["global_batch"]
            return 2.0 * n_act * D
        return 2.0 * n_act * shape.params["global_batch"]   # decode: 1 tok
    if cfg.family == "gnn":
        # per-edge message MLP + per-node update, x3 for fwd+bwd
        p = shape.params
        E = 2 * p.get("n_edges", p.get("batch", 1) * p.get("n_edges", 64))
        d = cfg.d_hidden
        return 3.0 * cfg.n_layers * (E * (6 * d * d) * 2)
    # recsys: embedding + MLPs per example
    cfgr = cfg
    B = shape.params.get("batch", 1) * max(
        shape.params.get("n_candidates", 1), 1)
    mlp_flops = 0
    dims = [8 * cfgr.embed_dim] + list(cfgr.mlp) + [1]
    for a, b in zip(dims[:-1], dims[1:]):
        mlp_flops += 2 * a * b
    return float(B) * mlp_flops * (3.0 if shape.kind == "train" else 1.0)


def run() -> list[str]:
    rows = ["arch,shape,mesh,chips,compute_s,memory_s,collective_s,"
            "dominant,bound_s,model_flops,hlo_flops,useful_ratio,"
            "mem_per_dev_GB,fits_16GB"]
    for f in sorted(DRYRUN_DIR.glob("*.json")):
        d = json.loads(f.read_text())
        if d.get("status") != "OK":
            if d.get("status") == "SKIP":
                rows.append(f"{d['arch']},{d['shape']},{d['mesh']},,,,,SKIP,"
                            f",,,,,{d.get('reason', '')}")
            continue
        r = d["roofline"]
        mem = d.get("memory", {})
        live = mem.get("per_device_live_bytes", 0) / 1e9
        try:
            mf = model_flops(d["arch"], d["shape"]) if d["arch"] != "kcore" \
                else 0.0
        except Exception:
            mf = 0.0
        ratio = round(mf / r["flops"], 3) if r["flops"] and mf else ""
        rows.append(",".join(str(x) for x in (
            d["arch"], d["shape"], d["mesh"], d.get("chips", ""),
            f"{r['compute_s']:.5f}", f"{r['memory_s']:.5f}",
            f"{r['collective_s']:.5f}", r["dominant"],
            f"{max(r['compute_s'], r['memory_s'], r['collective_s']):.5f}",
            f"{mf:.3e}" if mf else "", f"{r['flops']:.3e}", ratio,
            f"{live:.2f}", mem.get("fits_16GB", ""))))
    return rows


# ---------------------------------------------------------------------- #
# Superstep roofline: achieved vs peak for the dispatched masked round
# ---------------------------------------------------------------------- #

def superstep_records(ns=(2000,), m_attach: int = 4,
                      dispatches=("xla", "pallas"), reps: int = 5) -> list:
    """Compile + time the dispatched masked superstep per (graph, dispatch).

    One record per pair: HLO flops / bytes from the compiled program's cost
    analysis, best-of-``reps`` wall, achieved rates, and the fraction of the
    device's peaks (``repro.platform.peaks``, keyed by ``device_kind``)
    those rates reach. A device without published peaks raises: there is
    no roofline to report for it.
    """
    import jax
    import jax.numpy as jnp

    from repro import platform
    from repro.core import dispatch as dmod
    from repro.core.kcore import _bs_iters
    from repro.graph.generators import barabasi_albert
    from repro.graph.structs import build_ell

    peak_flops, peak_bw = platform.peaks()
    backend = jax.default_backend()
    records = []
    for n in ns:
        g = barabasi_albert(int(n), m_attach, seed=0)
        n_iters = _bs_iters(g.max_deg)
        est = jnp.asarray(g.deg, jnp.int32)
        amask = jnp.ones(g.num_arcs, bool)
        act = jnp.ones(g.n, bool)
        for mode in dispatches:
            plan = dmod.DispatchPlan(kind=mode,
                                     interpret=platform.interpret_kernels())
            ell = build_ell(g) if mode == "pallas" else None
            prog = dmod.masked_round_program(g.n, n_iters, plan,
                                             g.src, g.dst, ell=ell)
            compiled = prog.lower(est, amask, act).compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            flops = float(ca.get("flops", 0.0) or 0.0)
            nbytes = float(ca.get("bytes accessed", 0.0) or 0.0)
            jax.block_until_ready(prog(est, amask, act))   # warmup
            wall = min(_timed_round(prog, est, amask, act)
                       for _ in range(max(reps, 1)))
            ach_flops = flops / wall if wall > 0 else 0.0
            ach_bw = nbytes / wall if wall > 0 else 0.0
            records.append({
                "graph": f"ba_{g.n}_{m_attach}", "n": g.n, "m": g.m,
                "backend": backend, "dispatch": mode,
                "interpret": bool(plan.interpret and mode == "pallas"),
                "n_iters": n_iters, "ms_per_round": wall * 1e3,
                "hlo_flops": flops, "hlo_bytes": nbytes,
                "achieved_gflops": ach_flops / 1e9,
                "achieved_gbs": ach_bw / 1e9,
                "peak_gflops": peak_flops / 1e9,
                "peak_gbs": peak_bw / 1e9,
                "frac_peak_flops": ach_flops / peak_flops,
                "frac_peak_bytes": ach_bw / peak_bw,
            })
    return records


def _timed_round(prog, est, amask, act) -> float:
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(prog(est, amask, act))
    return time.perf_counter() - t0


def superstep_rows(records: list) -> list[str]:
    cols = ("graph", "n", "m", "backend", "dispatch", "interpret",
            "ms_per_round", "hlo_flops", "hlo_bytes", "achieved_gflops",
            "achieved_gbs", "peak_gflops", "peak_gbs", "frac_peak_flops",
            "frac_peak_bytes")
    rows = [",".join(cols)]
    for r in records:
        vals = []
        for c in cols:
            v = r[c]
            if isinstance(v, float):
                v = f"{v:.4g}"
            vals.append(str(v))
        rows.append(",".join(vals))
    return rows


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--superstep", action="store_true",
                    help="measure the dispatched masked superstep instead "
                         "of aggregating dry-run artifacts")
    ap.add_argument("--n", type=int, nargs="+", default=[2000],
                    help="graph sizes (barabasi-albert) for --superstep")
    ap.add_argument("--m-attach", type=int, default=4)
    ap.add_argument("--dispatch", nargs="+", default=["xla", "pallas"],
                    choices=["xla", "pallas"])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--json", metavar="PATH",
                    help="also write the records as JSON")
    args = ap.parse_args()
    if args.superstep:
        records = superstep_records(ns=args.n, m_attach=args.m_attach,
                                    dispatches=tuple(args.dispatch),
                                    reps=args.reps)
        rows = superstep_rows(records)
        if args.json:
            pathlib.Path(args.json).write_text(json.dumps(records, indent=2))
    else:
        rows = run()
    for r in rows:
        print(r)


if __name__ == "__main__":
    main()
