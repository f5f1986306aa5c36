"""Benchmark driver: one module per paper figure/table + kernels + the
simulator-throughput benchmark (``simperf``).

Usage:
    PYTHONPATH=src python -m benchmarks.run [--fast] [--fleet] [--only fig5,...]
    PYTHONPATH=src python -m benchmarks.run --list

``--fleet`` additionally runs fig9's 32-node / 22k-request fleet scenario.
With ``--list`` (or an unknown ``--only`` target) the driver prints the
available targets with one-line descriptions instead of erroring bare.
Prints ``name,seconds,derived`` CSV lines at the end.

Targets (the README's figure-reproduction table is generated from these):

    fig4          prefill/decode latency vs per-GPU power cap (paper Fig. 4)
    fig5          static SLO attainment vs request rate (paper Fig. 5)
    fig6          TTFT decomposition: queueing vs execution (paper Fig. 6)
    fig7          SLO-scale sweep at fixed QPS/GPU (paper Fig. 7)
    fig8          dynamic RAPID on the two-phase Sonnet workload (paper Fig. 8-9)
    fig9cluster   1-8 node cluster scaling under a facility power budget
    fig10hetero   heterogeneous nodes + cluster-scale DynGPU role flips
    fig11fleet    elastic fleet under diurnal load and node churn
    fig12autoscale predictive autoscaling on a price/carbon tariff
    fig13chaos    chaos replay: graceful degradation vs naive handling
    fig14control  control-plane chaos: fail-safe vs oracle vs naive control
    fig15multitenant multi-tenant day: SLO classes + preemption + locality
    simperf       simulator event-throughput benchmark (perf gate)
    kernels       interpret-mode Pallas kernel microbenchmarks vs jnp oracles
    beyond        beyond-paper ablation studies
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

SUITES = ("fig4", "fig5", "fig6", "fig7", "fig8", "fig9cluster",
          "fig10hetero", "fig11fleet", "fig12autoscale", "fig13chaos",
          "fig14control", "fig15multitenant", "simperf", "kernels",
          "beyond")

# one-liners for --list / unknown-target help, same order as SUITES
DESCRIPTIONS = {
    "fig4": "prefill/decode latency vs per-GPU power cap (paper Fig. 4)",
    "fig5": "static SLO attainment vs request rate (paper Fig. 5)",
    "fig6": "TTFT decomposition: queueing vs execution (paper Fig. 6)",
    "fig7": "SLO-scale sweep at fixed QPS/GPU (paper Fig. 7)",
    "fig8": "dynamic RAPID on the two-phase Sonnet workload (paper Fig. 8-9)",
    "fig9cluster": "1-8 node cluster scaling under a facility power budget",
    "fig10hetero": "heterogeneous nodes + cluster-scale DynGPU role flips",
    "fig11fleet": "elastic fleet under diurnal load and node churn",
    "fig12autoscale": "predictive autoscaling on a price/carbon tariff",
    "fig13chaos": "chaos replay: graceful degradation vs naive handling",
    "fig14control": "control-plane chaos: fail-safe vs oracle vs naive control",
    "fig15multitenant": "multi-tenant day: SLO classes + preemption + locality",
    "simperf": "simulator event-throughput benchmark (perf gate)",
    "kernels": "interpret-mode Pallas kernel microbenchmarks vs jnp oracles",
    "beyond": "beyond-paper ablation studies",
}


def print_targets(header: str = "Available targets:") -> None:
    print(header)
    for name in SUITES:
        print(f"  {name:15s} {DESCRIPTIONS[name]}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced request counts / rate grids")
    ap.add_argument("--fleet", action="store_true",
                    help="include fig9's 32-node fleet scenario")
    ap.add_argument("--list", action="store_true",
                    help="print available targets and exit")
    ap.add_argument("--only", default=None,
                    help="comma-separated target subset (see --list)")
    ap.add_argument("--seed", type=int, default=None,
                    help="scenario seed for the seeded targets "
                         "(fig13chaos, fig14control, fig15multitenant); "
                         "default: each module's built-in seed")
    args = ap.parse_args()
    if args.list:
        print_targets()
        return
    only = set(args.only.split(",")) if args.only else set(SUITES)
    unknown = only - set(SUITES)
    if unknown:
        print_targets(f"Unknown target(s): {sorted(unknown)}. "
                      f"Available targets:")
        raise SystemExit(2)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (beyond_ablations, fig4_power_curves,
                            fig5_static_slo, fig6_queueing, fig7_slo_scaling,
                            fig8_dynamic, fig9_cluster_scaling,
                            fig10_hetero_dyngpu, fig11_elastic_fleet,
                            fig12_autoscale_tariff, fig13_chaos,
                            fig14_control_chaos, fig15_multitenant,
                            kernels_bench, sim_throughput)
    mods = {
        "fig4": fig4_power_curves, "fig5": fig5_static_slo,
        "fig6": fig6_queueing, "fig7": fig7_slo_scaling,
        "fig8": fig8_dynamic, "fig9cluster": fig9_cluster_scaling,
        "fig10hetero": fig10_hetero_dyngpu,
        "fig11fleet": fig11_elastic_fleet,
        "fig12autoscale": fig12_autoscale_tariff, "fig13chaos": fig13_chaos,
        "fig14control": fig14_control_chaos,
        "fig15multitenant": fig15_multitenant,
        "simperf": sim_throughput,
        "kernels": kernels_bench,
        "beyond": beyond_ablations,
    }
    results = []
    failed = []
    for name in SUITES:
        if name not in only:
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.perf_counter()
        try:
            kw = {"fleet": True} if (args.fleet and name == "fig9cluster") \
                else {}
            if args.seed is not None and name in ("fig13chaos",
                                                  "fig14control",
                                                  "fig15multitenant"):
                kw["seed"] = args.seed
            out = mods[name].main(fast=args.fast, **kw)
            n = len(out) if hasattr(out, "__len__") else 1
            results.append((name, time.perf_counter() - t0, n))
        except Exception:
            traceback.print_exc()
            failed.append(name)
    print("\nname,seconds,derived")
    for name, dt, n in results:
        print(f"{name},{dt:.1f},{n}")
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
