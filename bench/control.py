#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 5

For each seed, in one process: set the cell up, run a short window at the
cell's own size and load, read the check's number for what the program
produced (the lower reading), then read the same number for the control,
the plain reference computed in float8 (e4m3) in the program's place: one
step below the bfloat16 the configuration serves in.  Prints one JSON line
per seed and a summary; the benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

CONTROL_DTYPE = "float8_e4m3fn"


def measure(workload: str, seed: int, seconds: float, **kw) -> dict:
    """Program and control readings of one seed (``kw``: harness.prepare)."""
    import jax.numpy as jnp
    from bench import harness
    run, driver = harness.prepare(workload, seed, seconds, False, **kw)
    session = driver.Session(run)
    run.facts = session.window(seconds)
    session.release()
    gc.collect()
    program, failed = session.check()
    control, _ = session.check(jnp.dtype(CONTROL_DTYPE))
    del session
    gc.collect()
    return {"seed": seed, "failed": failed,
            "program": {k: v for k, (v, _) in program.items()},
            "control": {k: v for k, (v, _) in control.items()},
            "attempted": run.facts["attempted"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(measure(args.workload, seed, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    for name in rows[0]["program"]:
        low = max(r["program"][name] for r in rows)
        up = min(r["control"][name] for r in rows)
        print(json.dumps({"number": name, "lower": low, "upper": up,
                          "ratio": up / low if low else None,
                          "seeds": len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
