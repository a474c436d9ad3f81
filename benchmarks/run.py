"""Benchmark driver: one section per paper table/figure + TPU-side benches.

Prints ``name,us_per_call,derived`` CSV rows (one per measured quantity).
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated table names (e.g. fig9,tab4)")
    ap.add_argument("--skip-tpu", action="store_true",
                    help="skip the TPU-framework benchmarks")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import paper_tables

    print("name,us_per_call,derived")
    t0 = time.time()
    failed = []
    for name, fn in paper_tables.ALL_TABLES:
        if only and name not in only:
            continue
        t = time.time()
        try:
            rows = fn()
        except Exception as exc:  # report the section, run the rest
            print(f"{name},0,ERROR {type(exc).__name__}: {exc}")
            failed.append(name)
            continue
        for row in rows:
            print(row)
        print(f"{name}.elapsed,{(time.time() - t) * 1e6:.0f},s={time.time() - t:.1f}",
              file=sys.stderr)

    if not args.skip_tpu and (only is None or "tpu" in only):
        try:
            from benchmarks import tpu_sectored
            rows = tpu_sectored.run_all()
        except Exception as exc:  # report the section, then fail the run
            print(f"tpu,0,ERROR {type(exc).__name__}: {exc}")
            failed.append("tpu")
        else:
            for row in rows:
                print(row)
    print(f"total.elapsed,{(time.time() - t0) * 1e6:.0f},"
          f"s={time.time() - t0:.1f}", file=sys.stderr)
    if failed:
        print(f"failed sections: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)

if __name__ == "__main__":
    main()
