"""The control: the plain reference put in the program's place with one
guarantee the configurations state broken, its answers handed to the
cell's own check (``Driver.check``) and verdict.  It must read as not
correct.

The broken guarantee is the line limit: every line up to 8,191 bytes is
parsed whole.  The control parses each line cut to its first 1,024
bytes, the step a change that capped the line bucket to cut padding
would take.  It runs at the cell's own size from the cell's own seed
(no chip: the reference is plain Python), and prints one JSON line per
seed with each number the check compares and ``correct``.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3
"""
import argparse
import json
import os
import shutil
import sys

import numpy as np

import run

CUT = 1024


def etl_answers(driver, cut):
    """One job's answers as the control gives them."""
    from reference import Reference

    JobAnswers = sys.modules[type(driver).__module__].JobAnswers
    lines, n = driver.lines, len(driver.lines)
    ref = Reference(driver.cfg["log_format"], driver.cfg["fields"])
    ops = driver.ops()
    _, want_rejects, want_aggs = driver.reference_scan()
    rejected = {i: lines[i] for i in want_rejects}
    aggs = [dict(d) for d in want_aggs]
    cut_rows = {}
    # The control differs from the reference only on lines longer than
    # the cut: parse those cut, and move their rows and counts.
    for i in np.flatnonzero(driver.lens > cut).tolist():
        full, short = ref.parse_line(lines[i]), ref.parse_line(lines[i][:cut])
        cut_rows[i] = short
        if short is None:
            rejected[i] = lines[i][:cut]
        else:
            rejected.pop(i, None)
        for k, op in enumerate(ops):
            for row, sign in ((full, -1), (short, 1)):
                if row is None or row[op["field"]] is None:
                    continue
                v = row[op["field"]]
                if op["op"] == "time_bucket":
                    v = v // (op["width_s"] * 1000)
                aggs[k][v] = aggs[k].get(v, 0) + sign
    for d in aggs:
        for key in [key for key, c in d.items() if c == 0]:
            del d[key]

    def row(i):
        return cut_rows[i] if i in cut_rows else ref.parse_line(lines[i])

    return JobAnswers(n, n - len(rejected), rejected, row=row,
                      aggs=aggs if ops else None)


def served_result(driver, cut):
    """The served comparison over the control's answers to the sampled
    requests (every request answered)."""
    from loadgen import build_requests, compare
    from reference import Reference

    spec = driver.load_spec()
    requests, lines_of, sample = build_requests(spec)
    ref = Reference(spec["log_format"], spec["fields"])
    invalid = dict({f: None for f in ref.fields}, __valid__=False)
    answers = {}
    for k in sample:
        rows = [ref.parse_line(ln[:cut]) for ln in lines_of[k]]
        answers[k] = [invalid if r is None else dict(r, __valid__=True)
                      for r in rows]
    return compare(ref, lines_of, answers, len(requests), len(requests))


def readings(workload, seed, seconds, rehearse=False, cut=CUT):
    """The cell's checks with the control's answers, and ``correct``."""
    from check import verdict

    bench, cell, config, traffic = run.cell_spec(workload)
    sys.path[:0] = [run.BENCH, run.ROOT]
    name = "bench_driver_" + config["driver"]
    mod = run.load_module(os.path.join(run.BENCH, "drivers",
                                       config["driver"] + ".py"), name)
    sys.modules[name] = mod
    workdir = os.path.join(run.ROOT, ".bench_control")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    try:
        driver = mod.Driver(config, traffic, seed, seconds, workdir,
                            rehearse=rehearse)
        if config["driver"] == "etl_job":
            driver.write_corpus()
            checks = driver.check(answers=[etl_answers(driver, cut)])
        else:
            checks = driver.check(result=served_result(driver, cut))
        return checks, verdict(checks)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float,
                    default=run.load_json(os.path.join(
                        run.ROOT, "BENCHMARK.json"))["run_seconds"])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    for seed in (int(s) for s in args.seeds.split(",")):
        checks, correct = readings(args.workload, seed, args.seconds,
                                   args.rehearse)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": correct,
                          **{k: c["value"] for k, c in checks.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
