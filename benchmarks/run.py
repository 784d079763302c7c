"""Benchmark orchestrator — one section per paper table/figure + roofline.

Prints ``name,...`` CSV sections.  Usage:
    PYTHONPATH=src python -m benchmarks.run [section ...]
"""

from __future__ import annotations

import sys
import time


def main() -> None:
    from benchmarks import (ablation_nodes, compression_table, freq_table,
                            roofline, speedup)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    sections = {
        "freq_table": freq_table.run,            # paper Table II / Fig 3
        "compression_table": compression_table.run,   # paper Table V
        "speedup": speedup.run,                  # paper §VI perf claims
        "ablation_nodes": ablation_nodes.run,    # beyond-paper design space
        "roofline_single": lambda: roofline.run("single"),
        "roofline_multi": lambda: roofline.run("multi"),
    }
    want = sys.argv[1:] or list(sections)
    failed = []
    for name in want:
        t0 = time.monotonic()
        print(f"\n== {name} ==")
        try:
            for row in sections[name]():
                print(row)
        except Exception as e:  # noqa: BLE001
            # report the section and keep going; the exit code says so
            print(f"# FAILED: {type(e).__name__}: {e}")
            failed.append(name)
        print(f"# ({time.monotonic() - t0:.1f}s)")
    if failed:
        sys.exit(f"failed sections: {', '.join(failed)}")


if __name__ == "__main__":
    main()
