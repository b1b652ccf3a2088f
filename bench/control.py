"""Readings that a cell's limits of ``correct`` are set from.

    python3 bench/control.py --workload qwen1.5-0.5b.bursty \
        --seeds 101,102,...,112 --seconds 50 --out readings/

For each seed, in one process: weights from the seed, the engine warmed as a
run warms it, one window of the cell's own traffic, then over the same
sample of finished requests that a run compares, through the harness's own
comparison (``harness.judge``):

* the program: its served tokens against the reference, as a run judges
  them, and
* the control: the reference with every matmul weight rounded to float8
  e4m3 (per-channel scales), teacher-forced over the same prompts and served
  tokens, its first choice at each position standing in for the served
  token.

Each row gives both sides' numbers and ``correct``.  For each number the
lower reading is the largest program reading over the seeds, the upper the
smallest control reading; a limit lies between them.  With ``--out`` the
per-token gaps of each seed, program and control, are kept as
``<out>/<seed>.npz``.  The benchmark's own runs do not run the control.
"""
import argparse
import gc
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    import numpy as np
    from bench import harness
    from repro.core.controller import FlexPipeController

    cell = harness.load_cell(a.workload)
    log = harness.CompileLog()
    out_dir = Path(a.out) if a.out else None
    if out_dir:
        out_dir.mkdir(parents=True, exist_ok=True)
    rows = []
    for seed in (int(s) for s in a.seeds.split(",")):
        p = harness.prepare(cell, seed, log=log)
        fill, arr = harness.make_traffic(p, cell.traffic, a.seconds, seed)
        ctl = (FlexPipeController(p.cfg, p.profiles) if p.profiles
               else None)
        srv = harness.serve(p, arr, fill, a.seconds, ctl,
                            harness.Spans(False))
        finished = [r for r in srv.records.values()
                    if not math.isnan(r.finished)]
        params = p.params
        del srv, p, ctl
        gc.collect()
        r = harness.readings(cell, params, finished, seed, control=True)
        row = {"seed": seed, "requests": len(r["requests"]),
               "tokens": len(r["served"])}
        for side, key in (("program", "served"), ("control", "control")):
            checks = harness.judge(cell, finished, r, key)
            row[side] = {"correct": harness.is_correct(checks),
                         **harness.logit_numbers(r[key])}
        if out_dir:
            np.savez_compressed(
                out_dir / f"{seed}.npz", served=r["served"],
                control=r["control"],
                lengths=np.array([len(q.tokens) for q in r["requests"]]))
        rows.append(row)
        print(json.dumps(row), flush=True)
        del params, r
        gc.collect()
    out = {"workload": a.workload, "rows": rows}
    for num in rows[0]["program"]:
        if num == "correct":
            continue
        out[num] = {"lower": max(q["program"][num] for q in rows),
                    "upper": min(q["control"][num] for q in rows)}
    out["control_correct_on"] = [q["seed"] for q in rows
                                 if q["control"]["correct"]]
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
