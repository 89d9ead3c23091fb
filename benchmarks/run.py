# One function per paper table. Print ``name,us_per_call,derived`` CSV.
import sys
import time


def main() -> None:
    from . import (cluster_selection, compression_quality, kernel_bench,
                   microbench_lora_fwd, recon_random_vs_trained,
                   roofline_report, serving_throughput)
    mods = [
        ("compression_quality", compression_quality),   # Fig 2/3, Tbl 7-14
        ("serving_throughput", serving_throughput),     # Fig 1/4
        ("microbench_lora_fwd", microbench_lora_fwd),   # Fig 5
        ("cluster_selection", cluster_selection),       # Fig 6 / App G
        ("recon_random_vs_trained", recon_random_vs_trained),  # Tbl 15
        ("kernel_bench", kernel_bench),
        ("roofline_report", roofline_report),           # deliverable (g)
    ]
    print("name,us_per_call,derived")
    for name, mod in mods:
        t0 = time.time()
        for row in mod.main(quick=True):
            print(row)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
