# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV. Mapping to the paper:
#   cold_vs_warm      -> Fig. 2 / Table 1 (cold/warm gap + stage breakdown)
#   kernel_table      -> Table 2 (per-kernel read/transform/cache/exec)
#   e2e_speedup       -> Fig. 8 / Table 5 (NNV12 vs baseline vs warm)
#   ablation          -> Fig. 13 (K / C / P knobs)
#   dynamic_load      -> Fig. 11 (background load + work stealing)
#   continuous        -> Fig. 14 (kernel switching, 1st/2nd/3rd inference)
#   plan_generation   -> Table 4 (offline decision time, storage overhead)
#   scheduler_quality -> §3.3 (Algorithm 1 vs optimal; annealing baseline)
#   shader_cache      -> §3.4 (XLA executable cache = shader cache)
#   core_sensitivity  -> beyond-paper: scheduler vs big/little asymmetry
#   roofline_report   -> EXPERIMENTS.md §Roofline (from the dry-run JSON)
#   io_formats        -> beyond-paper: per-tensor npy vs packed bundle vs
#                        zero-copy mmap bundle cold-read comparison
import sys
import time


def main() -> None:
    from repro.core.compile_cache import setup_compile_cache

    setup_compile_cache()
    from benchmarks import (
        ablation, cold_vs_warm, continuous, core_sensitivity, dynamic_load,
        e2e_speedup, io_formats, kernel_table, plan_generation,
        roofline_report, scheduler_quality, shader_cache,
    )

    benches = [
        ("io_formats", io_formats.run),
        ("kernel_table", kernel_table.run),
        ("cold_vs_warm", cold_vs_warm.run),
        ("e2e_speedup", e2e_speedup.run),
        ("ablation", ablation.run),
        ("dynamic_load", dynamic_load.run),
        ("continuous", continuous.run),
        ("plan_generation", plan_generation.run),
        ("scheduler_quality", scheduler_quality.run),
        ("shader_cache", shader_cache.run),
        ("core_sensitivity", core_sensitivity.run),
        ("roofline_report", roofline_report.run),
    ]
    print("name,us_per_call,derived")
    for name, fn in benches:
        t0 = time.time()
        try:
            fn(print_csv=True)
        except Exception as e:  # keep the suite going; report the failure
            print(f"{name}/ERROR,0,{type(e).__name__}:{str(e)[:120]}",
                  file=sys.stdout)
        print(f"# {name} finished in {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
