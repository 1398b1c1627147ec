"""Benchmark harness — one benchmark per paper table (+ kernel µbenches).
Prints ``name,us_per_call,derived`` CSV lines per the repo contract, then
writes a JSON blob with the full results.
"""
from __future__ import annotations

import json
import time


def main() -> None:
    results = {}
    t0 = time.perf_counter()

    from benchmarks import bench_table1
    r1 = bench_table1.run()
    results["table1_runtime_prediction"] = r1
    print(f"table1.loglinear_l1,{r1['loglinear_l1_s']*1e6:.0f},"
          f"variance_explained={r1['variance_explained']:.4f}")
    print(f"table1.averaging_l1,{r1['averaging_l1_s']*1e6:.0f},baseline")

    from benchmarks import bench_table23
    r23 = bench_table23.run_multi()
    results["table23_autoprovision"] = r23
    for row in r23["rows"]:
        sp = row.get("t2_speedup")
        sv = row.get("t3_cost_saving")
        print(f"table2.steps{row['steps']},"
              f"{(row['t2_runtime_s'] or 0)*1e6:.0f},"
              f"speedup={sp:.2f}x_paper=1.74x" if sp else
              f"table2.steps{row['steps']},0,infeasible")
        print(f"table3.steps{row['steps']},"
              f"{(row['t3_runtime_s'] or 0)*1e6:.0f},"
              f"cost_saving={sv*100:.1f}%_paper=38.8%" if sv is not None
              else f"table3.steps{row['steps']},0,infeasible")

    from benchmarks import bench_usability
    ru = bench_usability.run()
    results["table56_usability"] = ru
    print(f"usability.manual,{ru['manual']['total_s']*1e6:.0f},"
          f"ops={ru['manual']['bookkeeping_ops']}")
    print(f"usability.acai,{ru['acai']['total_s']*1e6:.0f},"
          f"ops={ru['acai']['bookkeeping_ops']},"
          f"tracking_cut={ru['tracking_time_reduction']*100:.0f}%")

    from benchmarks import bench_scheduler
    rs = bench_scheduler.run()
    results["scheduler"] = rs
    bench_scheduler.report(rs)

    from benchmarks import bench_kernels
    rk = bench_kernels.run()
    results["kernels"] = rk
    for row in rk:
        print(f"kernel.{row['kernel']},{row['us_per_call_interpret']:.0f},"
              f"max_err={row['max_err']:.2e}")

    print(f"total.wall,{(time.perf_counter()-t0)*1e6:.0f},seconds="
          f"{time.perf_counter()-t0:.1f}")
    with open("bench_results.json", "w") as f:
        json.dump(results, f, indent=1, default=str)


if __name__ == "__main__":
    main()
