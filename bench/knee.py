"""Find a cell's knee: the highest arrival rate its configuration sustains
under the cell's length mix.  Run once per configuration on the chip when a
cell is defined; the result goes into the traffic file as a number.

    python3 bench/knee.py --workload qwen1.5-110b-pp20.decode-heavy \
        --seconds 30 --seed 7

One engine, built and warmed as a run builds it, with the controller off.
The batch is filled and kept full by arrivals far above any sustainable
rate; the knee is the tokens/s completed at full batch over the mix's mean
output length: the arrival rate whose output tokens the full batch just
keeps up with.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    a = ap.parse_args()
    from bench import harness
    from bench.traffic_gen import expected_output_tokens

    cell = harness.load_cell(a.workload)
    p = harness.prepare(cell, a.seed)
    print(f"knee: {a.workload} on {p.dev.device_kind}, set-up "
          f"{sum(p.split.values()):.1f} s", flush=True)
    tr = dict(cell.traffic)
    mean_out = expected_output_tokens(tr["output"])
    # arrivals far above any sustainable rate keep every slot busy
    tr["arrivals"] = {"mean_rate": 4.0 * p.eng.ecfg.max_batch / a.seconds,
                      "phases": [{"seconds": 10, "rate_x": 1.0}]}
    tr["fill_batch"] = True
    fill, arr = harness.make_traffic(p, tr, a.seconds, a.seed)
    srv = harness.serve(p, arr, fill, a.seconds, None, harness.Spans(False))
    toks = sum(1 for r in srv.records.values() for t in r.stamps
               if 0 <= t < a.seconds)
    tps = toks / a.seconds
    print(json.dumps({"workload": a.workload,
                      "tokens_per_s_full_batch": tps,
                      "mean_output_tokens": mean_out,
                      "knee_rps": tps / mean_out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
