"""Readings that the limits of the check are set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20 \
        [--kernel fused_q8] [--out <file.jsonl>]

Runs the cell once per seed in one process, as ``bench/run.py`` does, and
prints one JSON line per seed: the program's widest gaps and, computed over
the same prompts and served tokens, those of the float8 control (the
reference one precision below bfloat16), each judged against the cell's
limits as ``bench/run.py`` judges a run. Exits 1 if the control reads
correct on any seed. ``--kernel fused_q8`` runs the program's own int8 KV
flavour instead of the cell's kernel, whose readings show beside the
control's. The benchmark's own runs never run a control.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--kernel", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        result = run.run_cell(spec, seed, args.seconds, False,
                              kernel=args.kernel, control=True)
        caught = caught and not result["control"]["correct"]
        line = json.dumps(dict(
            workload=args.workload, seed=seed, kernel=result["info"]["kernel"],
            program=result["check"], program_correct=result["correct"],
            control=result["control"]["check"],
            control_correct=result["control"]["correct"], info=result["info"],
            metrics={k: v["value"] for k, v in result["metrics"].items()},
            memory_peak_bytes=result["device"]["memory_peak_bytes"]))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        gc.collect()
    if not caught:
        print("the control read correct on some seed", file=sys.stderr)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
