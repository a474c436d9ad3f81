"""Find the highest arrival rate an open-loop cell sustains, on the chip.

    python3 bench/sweep.py --workload yi6b-chat-short --rates 0.5,1,2 \
        --seconds 30 --seed 1

Runs the cell once per rate in one process, with the traffic file's
``rate_per_s`` replaced, and prints one JSON line per rate: completions,
the backlog (requests submitted and not finished) at the start and the end
of the window, and the end-to-end metrics. A rate is sustained when the
backlog does not grow over the window. The cell's rate is then fixed in its
traffic file; the benchmark itself never searches.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        spec.traffic = dict(spec.traffic, rate_per_s=rate)
        result = run.run_cell(spec, args.seed, args.seconds, False)
        print(json.dumps(dict(
            rate_per_s=rate, correct=result["correct"],
            attempted=result["attempted"], info=result["info"],
            metrics={k: v["value"] for k, v in result["metrics"].items()})),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
