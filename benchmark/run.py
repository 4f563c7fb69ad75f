#!/usr/bin/env python3
"""One run of one cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and per-layer metrics are found by
name from BENCHMARK.json (benchmark/README.md says how to add one with
new files only). This process never imports jax; its children hold the
chip one after another. Without the accelerator the cell asks for it
exits non-zero and prints no result. The last line of stdout is the one
JSON object; the numbers compared for `correct` are the last lines of
stderr and the object's last key.

    --rehearse   tiny sizes on the CPU through every child; never prints
                 a result; exits 3
    --control    (tools, tests; never the driver) also runs the control
    --fault X    (tests) breaks the timed path underneath
"""

import argparse
import sys

import harness

KINDS = {"jobs": "cell_train", "closed_loop": "cell_serve",
         "open_loop": "cell_serve"}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)
    harness.require_program()
    spec = harness.load_cell(args.workload)
    if args.seconds is None:
        args.seconds = float(spec["run_seconds"])
    if args.rehearse:
        args.seconds = min(args.seconds, spec["traffic"]["rehearse_seconds"])
    kind = spec["traffic"]["kind"]
    if kind not in KINDS:
        harness.fail(f"traffic kind {kind!r} has no generator "
                     f"(have: {', '.join(sorted(KINDS))})")
    module = __import__(KINDS[kind])
    result = module.run(spec, args)
    if "jax" in sys.modules:
        harness.fail("the parent process imported jax")
    if args.rehearse:
        harness.log("rehearsal", **{k: v for k, v in result.items()
                                    if k != "reference"})
        print("run.py: rehearsal complete; a rehearsal prints no result",
              file=sys.stderr)
        return 3
    harness.emit_result(result["correct"], result["attempted"],
                        result["failed"], result["metrics"], result["device"],
                        result["compared"], result.get("breakdown"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
