"""Benchmark driver: one function per paper table/figure + beyond-paper
extensions.  Prints ``name,...`` CSV blocks; exits non-zero on any failure."""
from __future__ import annotations

import sys
import time


def main() -> None:
    from benchmarks import (
        ablation_schemes,
        fig2_synthetic,
        fig3_movielens,
        fig5_acc_vs_sparsity,
        gam_head_bench,
        speedup_table,
    )

    failures = []
    for name, mod in (
        ("fig2_synthetic", fig2_synthetic),
        ("fig3_movielens", fig3_movielens),
        ("fig5_acc_vs_sparsity", fig5_acc_vs_sparsity),
        ("speedup_table", speedup_table),
        ("gam_head_bench", gam_head_bench),
        ("ablation_schemes", ablation_schemes),
    ):
        t0 = time.monotonic()
        try:
            mod.main()
            print(f"# {name} done in {time.monotonic() - t0:.1f}s\n")
        except Exception as e:  # pragma: no cover
            import traceback
            traceback.print_exc()
            failures.append((name, e))
    if failures:
        print("FAILURES:", failures)
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
