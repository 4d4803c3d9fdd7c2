"""Run every cell of BENCHMARK.json here, on the CPU, at its
configuration's tiny size (``run.py --rehearse``): the whole path of a
run (set-up, window, check) without the chip.  Each result is labelled
as a CPU rehearsal and carries no device metric.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [--seconds 3]
"""
import argparse
import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seconds", default="3")
    ap.add_argument("--seed", default=str(2**33 + 1))
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    failed = 0
    for cell in cells:
        for trace in ("0", "1"):
            p = subprocess.run(
                [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
                 cell, "--seed", args.seed, "--seconds", args.seconds,
                 "--trace", trace, "--rehearse"],
                cwd=ROOT, env=env, capture_output=True, text=True)
            last = p.stdout.strip().splitlines()[-1:] or [""]
            ok = p.returncode == 0 and json.loads(last[0] or "{}").get("correct")
            failed += not ok
            print(f"{cell} trace={trace} rc={p.returncode}: {last[0][:300]}",
                  flush=True)
            if not ok:
                print(p.stderr[-3000:], flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
