"""Find the knee of a served cell: the highest offered rate at which the
backlog does not grow.  One process, one set-up, then one window per
rate, lowest first, each with a fresh load generator (on the chip:
``chiprun -- python3 benchmark/sweep.py --workload <cell> --seed <n>
--seconds <s> --rates 2000,4000,...``).  Prints one JSON line per rate,
then the knee: the highest rate, below the first that failed, at which
every request was answered, the answers ended within 2 s of the window
and the median wait of the last quarter of requests was under 1.5 times
the first quarter's; and the offer, 4/5 of the knee rounded down to
1,000 lines/s, which goes into the traffic file by hand and into
PERF.md.  Not part of a benchmark run."""
import argparse
import json
import os
import shutil
import sys

import run


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    rates = [float(r) for r in args.rates.split(",")]
    bench, cell, config, traffic = run.cell_spec(args.workload)
    run.pin_caches()
    sys.path[:0] = [run.BENCH, run.ROOT]
    mod = run.load_module(os.path.join(run.BENCH, "drivers",
                                       config["driver"] + ".py"), "drv")
    shutil.rmtree(run.WORK_DIR, ignore_errors=True)
    os.makedirs(run.WORK_DIR)
    traffic = json.loads(json.dumps(traffic))
    traffic["arrivals"]["lines_per_s"] = rates[0]
    driver = mod.Driver(config, traffic, args.seed, args.seconds,
                        run.WORK_DIR, rehearse=args.rehearse)
    try:
        run.device_check(cell["chips"], args.rehearse)
        counter = run.CompileCounter()
        driver.setup()
        print(json.dumps({"setup_s": run.time.monotonic() - run.T_START}),
              file=sys.stderr, flush=True)
        knee = None
        for k, rate in enumerate(rates):
            if k:
                driver.child.stdin.close()
                driver.child.wait(timeout=60)
                driver.arrivals["lines_per_s"] = rate
                driver._start_child()
                if driver.child.stdout.readline().strip() != "READY":
                    raise RuntimeError("load generator did not start")
            n0 = counter.n
            driver.window(args.seconds)
            s = driver.summary
            print(json.dumps({"lines_per_s": rate,
                              "compiles": counter.n - n0, **s}), flush=True)
            held = (s["failed"] == 0 and s["span_s"] < args.seconds + 2
                    and s["backlog_growth"] < 1.5)
            if held and knee == (rates[k - 1] if k else None):
                knee = rate
        offer = None if knee is None else int(0.8 * knee) // 1000 * 1000
        print(json.dumps({"knee": knee, "offer": offer}), flush=True)
    finally:
        driver.close()
        shutil.rmtree(run.WORK_DIR, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
