"""Benchmark harness — one module per paper table.

``python -m benchmarks.run [table ...]`` prints ``name,us_per_call,derived``
CSV rows (and writes benchmarks/results.csv).
"""
from __future__ import annotations

import importlib
import sys
import time

TABLES = ["t2_driver_epsilon", "t3_epsilon_methods", "t4_datasize",
          "t5_clusters", "t6_datasets", "t7_accuracy", "t8_silhouette",
          "t9_kernel", "t10_stream", "t11_engine", "t12_cache",
          "t13_roofline", "t16_tenant"]


def main() -> None:
    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    tables = args or TABLES
    import json

    from repro.launch.cache import enable_compile_cache

    from .common import ROWS, ROWS_META, emit
    enable_compile_cache()
    print("name,us_per_call,derived")
    for t in tables:
        mod = importlib.import_module(f"benchmarks.{t}")
        t0 = time.perf_counter()
        mod.run()
        emit(f"{t}/total_wall", (time.perf_counter() - t0) * 1e6, "")
    with open("benchmarks/results.csv", "w") as f:
        f.write("name,us_per_call,derived\n")
        f.write("\n".join(ROWS) + "\n")
    # the same rows with structured platform/backend/interpret metadata
    with open("benchmarks/results_meta.json", "w") as f:
        json.dump(ROWS_META, f, indent=1)


if __name__ == "__main__":
    main()
