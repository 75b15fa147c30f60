"""One run of one cell: set up, measure for ``--seconds``, check, report.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's mix names the loop that drives it:

* ``decompose`` — back-to-back static decompositions through
  ``repro.launch.kcore_run.decompose``, each of the configuration's graph
  relabelled afresh (``bench.graphs.group_permutation``);
* ``serve`` — one writer applying churn batches back to back through the
  server's ``update``.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with its limit).
The same checks are the last lines of standard error. No TPU, or fewer
chips than the cell asks for: exit 2 and no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import shutil
import sys
import time

import numpy as np

from bench import device, graphs, reference, spec, traffic, work

SETUP_METRIC = "setup_s"


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")


@dataclasses.dataclass
class Run:
    """What a run measured, handed to the per-layer metric readers."""

    cell: str
    steps: list  # one dict per timed call in the window
    window_s: float
    window_compiles: int
    reduced: object = None  # bench.devtrace.Reduced (traced runs)
    shapes: work.KernelShapes | None = None
    peaks: dict | None = None


def configure_jax(root: pathlib.Path) -> None:
    """Persistent compilation cache at a fixed path: the directory jax is
    given in ``JAX_COMPILATION_CACHE_DIR``, else ``bench/.jax_cache`` in
    the checkout. Every program is cached, however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip() or str(root / "bench" / ".jax_cache")
    pathlib.Path(path).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def graph_cache(root: pathlib.Path, config: dict) -> pathlib.Path:
    return root / "bench" / ".cache" / f"{config['name']}-g{config['graph_seed']}.npz"


def program_graph(g: graphs.CSR):
    """The program's input type over the benchmark's CSR (no copy)."""
    from repro.graph.structs import Graph

    return Graph(n=g.n, m=g.m, src=g.src, dst=g.dst, offsets=g.offsets, deg=g.deg)


class Window:
    """The measured window, optionally under the profiler."""

    def __init__(self, traced: bool, log_dir: pathlib.Path):
        self.traced, self.log_dir = traced, log_dir

    def __enter__(self):
        import jax

        from repro.core.jit_telemetry import compile_count

        self._count = compile_count
        if self.traced:
            shutil.rmtree(self.log_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.log_dir))
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.compiles0 = compile_count()
        self.t_open = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t_open
        self.compiles = self._count() - self.compiles0
        self._span.__exit__(*exc)
        if self.traced:
            import jax

            jax.profiler.stop_trace()
        return False


# ---------------------------------------------------------------------- #
# decompose loop
# ---------------------------------------------------------------------- #


def run_decompose(root, cell, seed, seconds, traced, log_dir):
    import jax

    from repro.launch import kcore_run

    g0 = graphs.load_or_generate(cell.config, graph_cache(root, cell.config))
    args = kcore_run.parse_args(cell.config["engine"]["kcore_run_args"])
    rng = traffic.stream_rng(seed, "relabel")
    outputs = []

    def step():
        with jax.profiler.TraceAnnotation("bench.prep"):
            p = graphs.group_permutation(g0.n, rng)
            g = program_graph(graphs.relabel(g0, p))
        with jax.profiler.TraceAnnotation("bench.decompose"):
            t0 = time.perf_counter()
            res, _ = kcore_run.decompose(args, g)
            wall = time.perf_counter() - t0
        outputs.append((p, res))
        return {"wall_s": wall, "rounds": res.rounds, "phase_s": dict(res.phase_s),
                "compiles": res.recompiles}

    for _ in range(int(cell.mix.get("warmup_calls", 1))):
        step()
    setup_s = process_age_s()
    steps = []
    with Window(traced, log_dir) as win:
        while time.perf_counter() - win.t_open < seconds:
            steps.append(step())
    state = {"g0": g0, "outputs": outputs}
    return setup_s, steps, win, state


def check_decompose(cell, state, root) -> tuple[dict, int, int]:
    """Cores and per-round bills of every decomposition against the
    reference of the configuration's graph (relabelling moves neither)."""
    ref = reference_of(cell, state["g0"], root)
    core_bad = bill_bad = failed = 0
    for p, res in state["outputs"]:
        core = np.asarray(res.core)[p] if res.core.shape == ref["core"].shape else None
        bad_c = int((core != ref["core"]).sum()) if core is not None else int(ref["core"].size)
        bad_b = _bill_diff(res.stats.messages_per_round, ref["messages"]) + _bill_diff(
            res.stats.active_per_round, ref["active"])
        core_bad += bad_c
        bill_bad += bad_b
        failed += bool(bad_c or bad_b)
    checks = {"core_mismatches": [core_bad, 0], "bill_mismatches": [bill_bad, 0]}
    return checks, len(state["outputs"]), failed


def _bill_diff(got, want) -> int:
    got, want = np.asarray(got, np.int64), np.asarray(want, np.int64)
    k = min(got.size, want.size)
    return int((got[:k] != want[:k]).sum()) + abs(got.size - want.size)


def reference_of(cell, g0: graphs.CSR, root) -> dict:
    """The plain reference of the configuration's graph, cached beside it."""
    path = graph_cache(root, cell.config).with_suffix(".ref.npz")
    if path.exists():
        with np.load(path) as z:
            return {k: z[k] for k in z.files}
    ref = reference.jacobi_bills(g0)
    bz = reference.bz_cores(g0)
    if not np.array_equal(bz, ref["core"]):
        raise RuntimeError("the reference's iteration and its peeling disagree")
    out = {"core": ref["core"], "messages": ref["messages"], "active": ref["active"]}
    tmp = path.with_suffix(".tmp.npz")
    np.savez(tmp, **out)
    tmp.replace(path)
    return out


# ---------------------------------------------------------------------- #
# serve loop
# ---------------------------------------------------------------------- #


def run_serve(root, cell, seed, seconds, traced, log_dir):
    import jax

    import repro.streaming as streaming

    eng = cell.config["engine"]
    g0 = graphs.load_or_generate(cell.config, graph_cache(root, cell.config))
    # the churn trace is fixed by the mix; --seed relabels the graph and it
    p = graphs.group_permutation(g0.n, traffic.stream_rng(seed, "relabel"))
    server = streaming.KCoreServer(program_graph(graphs.relabel(g0, p)),
                                   streaming.StreamingConfig(frontier=eng["frontier"]))
    churn = cell.mix["churn"]
    stream = traffic.ChurnStream(g0, churn["frac"], churn["stream_seed"])
    outputs = []  # (state index after the batch, core in the program's ids)

    def batch_step():
        with jax.profiler.TraceAnnotation("bench.prep"):
            b = stream.next_batch()
            eb = streaming.EdgeBatch.make(insert=p[b.insert], delete=p[b.delete])
        with jax.profiler.TraceAnnotation("bench.update"):
            t0 = time.perf_counter()
            res = server.update(eb)
            wall = time.perf_counter() - t0
        outputs.append((len(stream.batches), np.array(res.core, np.int32)))
        return {"wall_s": wall, "patch_s": res.patch_s, "seed_s": res.seed_s,
                "converge_s": res.converge_s, "compiles": res.recompiles, "mode": res.mode,
                "rounds": res.rounds, "seed_strategy": res.seed_strategy}

    for _ in range(int(churn["warmup_batches"])):
        batch_step()
    setup_s = process_age_s()
    steps = []
    with Window(traced, log_dir) as win:
        while time.perf_counter() - win.t_open < seconds:
            steps.append(batch_step())
    state = {"g0": g0, "perm": p, "stream": stream, "outputs": outputs}
    return setup_s, steps, win, state


def check_serve(cell, state, root) -> tuple[dict, int, int]:
    """Every batch fixpoint against BZ of the edge set the stream built."""
    g0, stream = state["g0"], state["stream"]
    cores: dict = {}

    def ref(i: int) -> np.ndarray:
        if i not in cores:
            g = g0 if i == 0 else graphs.csr_from_keys(g0.n, stream.batches[i - 1].keys_after)
            cores[i] = reference.bz_cores(g)
        return cores[i]

    p = state["perm"]
    core_bad = failed = 0
    for i, core in state["outputs"]:
        bad = int((core[p] != ref(i)).sum()) if core.shape == ref(i).shape else int(ref(i).size)
        core_bad += bad
        failed += bool(bad)
    return {"core_mismatches": [core_bad, 0]}, len(state["outputs"]), failed


LOOPS = {"decompose": (run_decompose, check_decompose), "serve": (run_serve, check_serve)}


# ---------------------------------------------------------------------- #
# the run
# ---------------------------------------------------------------------- #


def end_to_end(name: str, run: Run, setup_s: float) -> float | None:
    if name == SETUP_METRIC:
        return setup_s
    if name in ("decompose_s", "update_s"):
        return float(np.mean([s["wall_s"] for s in run.steps])) if run.steps else None
    raise KeyError(f"no end-to-end metric {name!r}")


def run_cell(root: pathlib.Path, cell: spec.Cell, seed: int, seconds: float, traced: bool) -> dict:
    configure_jax(root)
    device.require_tpu(cell.chips)
    import jax

    devs = jax.devices()
    shapes = work.KernelShapes()
    uninstall = shapes.install()
    loop, check = LOOPS[cell.mix["loop"]]
    log_dir = root / "bench" / ".trace" / cell.name
    try:
        setup_s, steps, win, state = loop(root, cell, seed, seconds, traced, log_dir)
    finally:
        uninstall()
    dev = device.summary(devs)
    checks, attempted, failed = check(cell, state, root)
    run = Run(cell=cell.name, steps=steps, window_s=win.seconds, window_compiles=win.compiles, shapes=shapes, peaks=device.peaks(dev["kind"]))
    out: dict = {}
    if traced:
        from bench import devtrace

        run.reduced = devtrace.reduce_planes(devtrace.load_planes(str(log_dir)))
        shutil.rmtree(log_dir, ignore_errors=True)
        dev["busy_s"] = run.reduced.busy_s
        dev["window_s"] = run.reduced.window_s
        metrics = {}
        for m in cell.per_layer:
            value = spec.load_reader(root, m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": run.reduced.device_ops, "idle_gaps": run.reduced.idle_gaps}
    else:
        metrics = {}
        for m in cell.end_to_end:
            value = end_to_end(m["name"], run, setup_s)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = bool(attempted) and all(v <= lim for v, lim in checks.values())
    line = {"correct": correct, "attempted": attempted, "failed": failed, "metrics": metrics,
            "device": dev, **out,
            "checks": {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}}
    walls = sorted(s["wall_s"] for s in steps)
    detail = {"calls": len(steps), "wall_s_min_median_max": walls and [walls[0], walls[len(walls) // 2], walls[-1]],
              "window_s": win.seconds, "window_compiles": win.compiles,
              "first_calls": steps[:12]}
    print("bench: " + json.dumps(detail, default=float), file=sys.stderr)
    return line


def emit(line: dict) -> None:
    for k, c in line["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def parse_args(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, root: pathlib.Path) -> int:
    args = parse_args(argv)
    cell = spec.load_cell(root, args.workload)
    try:
        line = run_cell(root, cell, args.seed, args.seconds, bool(args.trace))
    except device.NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    emit(line)
    return 0
