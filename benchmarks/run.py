# One function per paper table. Print ``name,us_per_call,derived`` CSV.
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import kernels, paper_tables, serving_e2e
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sections = [
        ("paper_tables (RowClone + D-RaNGe reproduction)", paper_tables.main),
        ("kernels", kernels.main),
        ("serving_e2e", serving_e2e.main),
    ]
    failed = []
    for name, fn in sections:
        print(f"### {name}")
        try:
            fn()
        except Exception:
            failed.append(name)
            traceback.print_exc()
        print()
    if failed:
        print(f"FAILED sections: {failed}")
        sys.exit(1)
    print("ALL BENCHMARK SECTIONS OK")


if __name__ == '__main__':
    main()
