"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Select subsets with
``python -m benchmarks.run [table1] [table3] [pipeline] [sampler] [fig5]
[presample] [kernels] [transformer] [roofline] [overlap_smoke]
[chaos_smoke]``.
"""
from __future__ import annotations

import sys
import time

# name -> (module, title[, run() kwargs]); the optional kwargs let an entry
# pin a module's gate configuration (e.g. the overlap smoke gate)
BENCHES = {
    "table1": ("benchmarks.table1_redundancy", "Table 1 — micro/mini redundancy"),
    "fig5": ("benchmarks.fig5_partition_quality", "Fig. 5 — partitioner quality"),
    "presample": ("benchmarks.presample_cost", "§7.3 — splitting algorithm cost"),
    "table3": ("benchmarks.table3_epoch_time", "Table 3 — epoch time breakdown"),
    "pipeline": ("benchmarks.pipeline_bench", "§5 — pipelined vs serial executor"),
    "sampler": ("benchmarks.sampler_bench", "§4 — host vs device sampling"),
    "kernels": ("benchmarks.kernel_bench", "Pallas kernels vs oracle"),
    "transformer": ("benchmarks.transformer_bench", "Assigned archs (reduced)"),
    "roofline": ("benchmarks.roofline_report", "Roofline from dry-run records"),
    # one tiny split-mode round with the overlap arms' exact-numerics/NaN
    # gate and the bf16 wire-byte reduction assert (DESIGN.md §3a); same
    # checks as `python -m benchmarks.pipeline_bench --smoke`
    "overlap_smoke": (
        "benchmarks.pipeline_bench",
        "§3a — overlap/wire-format smoke gate",
        {"modes": ("split",), "dataset": "tiny", "rounds": 1, "smoke": True},
    ),
    "mesh": ("benchmarks.mesh_bench", "§9 — 2D mesh scaling (R×P sweep)"),
    # one tiny round over every R×P factorization with the mesh gates
    # enforced: R=1 mesh bitwise == legacy 1D split, NaN-free everywhere,
    # zero steady-state recompiles across swept shapes; same checks as
    # `python -m benchmarks.mesh_bench --smoke`
    "mesh_smoke": (
        "benchmarks.mesh_bench",
        "§9 — 2D mesh numerics/recompile smoke gate",
        {"dataset": "tiny", "rounds": 1, "smoke": True},
    ),
    # reduced fig5 run with the qualitative partitioner gates (gsplit < rand
    # cross edges, replication strictly reduces wire bytes) enforced; same
    # checks as `python -m benchmarks.fig5_partition_quality --smoke`
    "fig5_smoke": (
        "benchmarks.fig5_partition_quality",
        "Fig. 5 — partitioner quality smoke gate",
        {"dataset": "tiny", "smoke": True},
    ),
    # the obs subsystem gate (docs/OBSERVABILITY.md): every plan-source
    # mode traced for two epochs — trace schema valid (no unclosed spans,
    # flow ids resolve, monotonic record order), trajectories bit-exact vs
    # the untraced twin, zero steady-state recompiles, and the disabled
    # path bounded under 1% of a step; same checks as
    # `python -m benchmarks.obs_smoke`
    "obs_smoke": (
        "benchmarks.obs_smoke",
        "§10 — tracing/metrics schema + overhead gate",
        {"smoke": True},
    ),
    # the fault-tolerance gate (docs/ROBUSTNESS.md): deterministic chaos —
    # kill-and-resume bitwise vs uninterrupted (serial + pipelined),
    # transient faults recovered inside the retry budget with no extra
    # recompiles, crashed producers respawned, corrupted checkpoints
    # detected with previous-good fallback, stalls raising the watchdog
    # within the timeout; same checks as `python -m benchmarks.chaos_smoke`
    "chaos_smoke": (
        "benchmarks.chaos_smoke",
        "§11 — fault-tolerance chaos smoke gate",
        {"smoke": True},
    ),
    # the splint static-analysis pass over the tree (docs/ANALYSIS.md):
    # per-family timing rows + a gate that fails on any unbaselined
    # finding; same checks as `python -m repro.analysis`
    "analysis": (
        "benchmarks.analysis_smoke",
        "splint — static-analysis smoke gate",
        {"smoke": True},
    ),
}


def main() -> None:
    import importlib

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    names = [a for a in sys.argv[1:] if a in BENCHES] or list(BENCHES)
    print("name,us_per_call,derived")
    failures = []
    for name in names:
        mod_name, title, *rest = BENCHES[name]
        kwargs = rest[0] if rest else {}
        t0 = time.perf_counter()
        try:
            mod = importlib.import_module(mod_name)
            for row in mod.run(**kwargs):
                print(row.csv(), flush=True)
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            print(f"{name}/FAILED,0.0,{e!r}", flush=True)
        print(
            f"# {name} ({title}) done in {time.perf_counter()-t0:.1f}s",
            flush=True,
        )
    if failures:
        raise SystemExit(f"{len(failures)} benchmark(s) failed: {failures}")


if __name__ == "__main__":
    main()
