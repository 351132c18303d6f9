"""Readings for the check's limit: run one cell on many seeds in one
process and, on each seed's sampled requests, read the program's widest
logit gap, then put the control (the reference one precision step down)
in the program's place and run the same comparison at the same limit.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 10

Prints one JSON line per seed and appends it to
``chiprun_out/calibrate.<workload>.jsonl``. Not part of a benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args(argv)
    from bench import check, harness
    out = os.path.join(ROOT, "chiprun_out",
                       f"calibrate.{args.workload}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    orig = check.run_check
    for seed in map(int, args.seeds.split(",")):
        got = {}

        def hooked(config, params, seed_, cands, limit=None):
            r = orig(config, params, seed_, cands, limit)
            t = time.perf_counter()
            got["control"] = orig(config, params, seed_, cands, limit,
                                  control=True)
            got["control_s"] = time.perf_counter() - t
            return r

        check.run_check = hooked
        t0 = time.perf_counter()
        res = harness.run(args.workload, seed, args.seconds,
                          bool(args.trace), t0,
                          os.path.join(ROOT, ".bench_runs", "calibrate"))
        line = dict(workload=args.workload, seed=seed,
                    program=res["check"]["max_logit_gap"]["value"],
                    tokens=res["check"]["checked_tokens"]["value"],
                    correct=res["correct"],
                    control=got["control"]["numbers"]["max_logit_gap"]
                    ["value"],
                    control_correct=got["control"]["correct"],
                    control_s=got["control_s"],
                    run_s=time.perf_counter() - t0,
                    metrics={k: v["value"] for k, v in res["metrics"].items()},
                    device=res["device"])
        print(json.dumps(line), flush=True)
        with open(out, "a") as f:
            f.write(json.dumps(line) + "\n")
    check.run_check = orig
    return 0


if __name__ == "__main__":
    sys.exit(main())
