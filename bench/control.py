"""The control of the correctness check, at a cell's own size.

    python3 bench/control.py --workload ycsb10m_40c.dgcc_hot64 --seeds 1,2,3

For each seed it takes the cell's first pool call and the cells that
``bench/run.py`` would check in it, simulates them with the reference
and with the control (the reference with isolation switched off: every
pending lock request granted at once, every planned predecessor taken as
committed), and prints the number of mismatched counters that the check
would report for the control. The benchmark's own runs never run this;
the check's limit (0 mismatches) has to lie below every reading here.
Runs on whatever device JAX gives (the chip, or the CPU for a small
cell); the last line is JSON.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seeds) -> list[dict]:
    from bench import run
    from bench.reference import core as ref

    out = []
    for seed in seeds:
        call = run.build_pool(cell, seed)[0]
        bad, cells = 0, []
        for _, i in run.sample([call], seed, run.CHECK_CELLS * cell.chips):
            c = call.cells[i]
            w = call.streams[c.stream]
            t0 = time.perf_counter()
            want = ref.simulate(ref.RefConfig(**c.engine), w)
            got = ref.simulate(
                dataclasses.replace(ref.RefConfig(**c.engine),
                                    isolation=False), w)
            n = len(run.mismatches(got, want))
            bad += n
            cells.append(dict(cell=i, protocol=c.protocol, mismatches=n,
                              commits=want["commits"],
                              control_commits=got["commits"],
                              seconds=time.perf_counter() - t0))
        out.append(dict(seed=seed, mismatches=bad, cells=cells))
        print(json.dumps(out[-1]), file=sys.stderr, flush=True)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated generator seeds")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".bench_cache", "jax")

    from bench import run
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = run.load_cell(args.workload)
    res = readings(cell, [int(s) for s in args.seeds.split(",")])
    print(json.dumps(dict(workload=args.workload, readings=res,
                          least=min(r["mismatches"] for r in res))))


if __name__ == "__main__":
    main()
