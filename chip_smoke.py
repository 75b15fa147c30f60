"""Bring-up smoke of the k-core engine and server on TPU at Table-I scale.

    python chip_smoke.py              # one chip: the phases below
    python chip_smoke.py --chips 4    # the 4-chip mesh path + its reference

One process runs every phase through the functions the CLIs call
(``repro.launch.kcore_run`` and ``repro.launch.kcore_serve``). Each graph
is generated once from its seed, and BZ core numbers are computed once per
distinct graph and shared by the phases. Each phase prints one JSON line
with phase, graph, n, m, dispatch, rounds, correct, compile_s and wall_s —
host-clock seconds with compilation included: a bring-up check, not a
benchmark. The last line of stdout is ``{"ok": true, "device": {...}}``
with the device as jax reports it. The serving loop's own CSV goes to
stderr.

Phases on one chip, in order:

  static-default  kcore_run --graph WG --scale 1.0 --fused (default dispatch)
  static-xla      the same with --dispatch xla: cores and per-round bills
                  bit-equal to static-default
  static-host     kcore_run --graph WG --scale 1.0 (the host round loop)
  serve-dense, serve-fused, serve-auto
                  kcore_serve --graph WG --scale 0.25 --batches 2 --verify
                  with --frontier dense | fused | auto

With ``--chips 4`` only two programs run: ``kcore_run --graph WG --scale
1.0 --fused --mesh 4`` (the fused shard_map while_loop on a 4-chip
("data",) mesh) and the single-chip fused run it must equal bit for bit.

Both graphs are cuts, forced by the time limit (1200 s for the whole
script, compilation included). On one TPU v5e a superstep is bound by
per-arc gathers and scatters: on the soc-LiveJournal1 analogue at scale
0.25 (32M arcs, 33 rounds) a round took ~1.8 s on the Pallas route and
~9.8 s on the XLA route (~300 ns per arc and round), and a dense serving
round on the full web-Google analogue (2^24 padded arcs) ~7 s, 231 s per
churn batch. At that rate the XLA route alone needs ~40 s per round on
full LJ1 (131M arcs), and ~20 s per round on com-lj or soc-pokec (~60M
arcs each), 600 s or more over 30 rounds before any other phase: so the
static phases run the largest Table-I analogue that fits with margin,
web-Google at scale 1.0. Six serving batches on full web-Google need
~1400 s, so the serving replays run web-Google at scale 0.25.

The script exits non-zero, before the last line, when no TPU is present,
a phase raises, a result disagrees with BZ, two dispatch routes disagree,
or a Pallas kernel would run interpreted (the plan says so, or the
compiled program holds no ``tpu_custom_call``).
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro import platform  # noqa: E402  (needs the src/ path above)

# (Table-I abbrev, scale): web-Google analogue — 1,048,576 vertices and
# 5,085,869 edges at scale 1.0; 262,144 vertices and 1,252,416 edges at 0.25
STATIC = ("WG", 1.0)
SERVE = ("WG", 0.25)


class Oracle:
    """BZ core numbers, computed once per distinct graph."""

    def __init__(self):
        self._memo: dict = {}
        self.seconds = 0.0

    def __call__(self, g):
        import numpy as np

        from repro.core import bz_core_numbers

        h = hashlib.blake2b(digest_size=16)
        for a in (np.int64(g.n), g.src, g.dst):
            h.update(np.ascontiguousarray(a).tobytes())
        key = h.hexdigest()
        if key not in self._memo:
            t0 = time.perf_counter()
            self._memo[key] = bz_core_numbers(g)
            self.seconds += time.perf_counter() - t0
        return self._memo[key]


def fail(msg: str):
    raise SystemExit(f"chip_smoke: {msg}")


def run_phase(name: str, graph: str, g, oracle: Oracle, fn) -> dict:
    """Run ``fn() -> dict(dispatch, rounds, correct, ...)``, print its line,
    and fail unless it is correct."""
    from repro.core import compile_seconds

    s0, b0 = compile_seconds(), oracle.seconds
    t0 = time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    row = {"phase": name, "graph": graph, "n": g.n, "m": g.m}
    row.update(out)
    row.update(compile_s=compile_seconds() - s0, wall_s=wall, bz_s=oracle.seconds - b0)
    print(json.dumps({k: v for k, v in row.items() if not k.startswith("_")}), flush=True)
    if row["correct"] is not True:
        fail(f"{name}: result disagrees with BZ")
    return row


def load_graph(abbrev: str, scale: float, oracle: Oracle):
    from repro.graph import generators

    t0 = time.perf_counter()
    g = generators.snap_analogue(abbrev, scale=scale, seed=0)
    gen_s = time.perf_counter() - t0
    b0 = oracle.seconds
    oracle(g)
    print(json.dumps({"setup": abbrev, "scale": scale, "n": g.n, "m": g.m, "max_deg": g.max_deg,
                      "gen_s": gen_s, "bz_s": oracle.seconds - b0}), flush=True)
    return g


def static_phase(argv, g, oracle):
    """kcore_run's path on ``g`` for the CLI arguments ``argv``."""
    from repro.launch import kcore_run

    args = kcore_run.parse_args(argv)
    platform.set_dispatch_mode(args.dispatch)
    try:
        res, wall = kcore_run.decompose(args, g)
    finally:
        platform.set_dispatch_mode(None)
    rep = kcore_run.report(args, g, res, wall, oracle(g))
    return {"dispatch": rep["dispatch"], "rounds": rep["rounds"], "correct": rep["correct_vs_BZ"],
            "devices": list(res.devices), "host_clock_phase_s": res.phase_s, "_res": res}


def same_bills(a, b) -> bool:
    import numpy as np

    return (a.rounds == b.rounds and np.array_equal(a.core, b.core)
            and all(np.array_equal(getattr(a.stats, f), getattr(b.stats, f))
                    for f in ("messages_per_round", "active_per_round", "changed_per_round")))


def check_native_pallas(g) -> int:
    """The default plan on this chip is the natively compiled Pallas route:
    its host-loop superstep program for ``g`` holds ``tpu_custom_call``
    (the same shapes the static-host phase ran, so the compile cache
    answers). Returns the number of kernel calls in the compiled text."""
    import jax.numpy as jnp

    from repro.core import KCoreConfig, dispatch
    from repro.core.kcore import _bs_iters
    from repro.graph.structs import build_ell

    plan = dispatch.resolve_plan()
    if plan.kind != "pallas" or plan.interpret:
        fail(f"default dispatch on TPU must be natively compiled Pallas, got {plan}")
    prog = dispatch.masked_round_program(g.n, _bs_iters(g.max_deg), plan, g.src, g.dst,
                                         ell=build_ell(g, widths=KCoreConfig().widths))
    text = prog.lower(jnp.asarray(g.deg, jnp.int32), jnp.ones(g.num_arcs, bool),
                      jnp.ones(g.n, bool)).compile().as_text()
    calls = text.count("tpu_custom_call")
    if not calls:
        fail("the Pallas-route program holds no tpu_custom_call")
    return calls


def serve_phase(mode: str, g, oracle):
    """kcore_serve's synthetic-churn loop on ``g`` with ``--frontier mode``."""
    from repro.core import dispatch
    from repro.launch import kcore_serve

    args = kcore_serve.parse_args(["--graph", SERVE[0], "--scale", str(SERVE[1]),
                                   "--batches", "2", "--verify", "--frontier", mode])
    with contextlib.redirect_stdout(sys.stderr):
        ticks = kcore_serve.serve_churn(args, g, oracle=oracle)
    return {"dispatch": dispatch.resolve_plan().kind, "rounds": sum(t["rounds"] for t in ticks),
            "correct": len(ticks) == args.batches and all(t["verified"] == "True" for t in ticks),
            "modes": [t["mode"] for t in ticks], "inc_messages": [t["inc_messages"] for t in ticks]}


STATIC_ARGS = ["--graph", STATIC[0], "--scale", str(STATIC[1])]


def one_chip(oracle: Oracle) -> None:
    g = load_graph(*STATIC, oracle)
    fused = STATIC_ARGS + ["--fused"]
    d = run_phase("static-default", STATIC[0], g, oracle, lambda: static_phase(fused, g, oracle))
    if d["dispatch"] != "pallas":
        fail(f"static-default ran {d['dispatch']!r}, not the Pallas route")
    x = run_phase("static-xla", STATIC[0], g, oracle,
                  lambda: static_phase(fused + ["--dispatch", "xla"], g, oracle))
    if not same_bills(d["_res"], x["_res"]):
        fail("static-default and static-xla disagree in cores or per-round bills")

    def host_loop():
        out = static_phase(STATIC_ARGS, g, oracle)
        out["tpu_custom_calls"] = check_native_pallas(g)
        return out

    run_phase("static-host", STATIC[0], g, oracle, host_loop)
    del g, d, x

    s = load_graph(*SERVE, oracle)
    for mode in ("dense", "fused", "auto"):
        run_phase(f"serve-{mode}", SERVE[0], s, oracle, lambda: serve_phase(mode, s, oracle))


def four_chips(oracle: Oracle) -> None:
    g = load_graph(*STATIC, oracle)
    fused = STATIC_ARGS + ["--fused"]
    s = run_phase("static-sharded", STATIC[0], g, oracle,
                  lambda: static_phase(fused + ["--mesh", "4"], g, oracle))
    if len(set(s["devices"])) != 4:
        fail(f"the sharded estimate lives on devices {s['devices']}, not on four")
    one = run_phase("static-default", STATIC[0], g, oracle, lambda: static_phase(fused, g, oracle))
    if not same_bills(s["_res"], one["_res"]):
        fail("the 4-chip and single-chip fused runs disagree in cores or per-round bills")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: every phase on one chip; 4: the mesh path and its reference only")
    args = ap.parse_args()

    platform.configure_run(platform="tpu", mesh=args.chips if args.chips > 1 else 0)
    oracle = Oracle()
    if args.chips == 4:
        four_chips(oracle)
    else:
        one_chip(oracle)
    print(json.dumps({"ok": True, "device": platform.device_summary()}), flush=True)


if __name__ == "__main__":
    main()
