"""The child that takes the chip once the training child has let go of
it: follows the checked job with the plain reference on the same
ratings and prints what compare.py makes of the two models. With
--control it ALSO runs the reference in bfloat16 (the control) in the
program's place, and the faults a training cell can have (the
benchmark's own runs never do).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

import compare
import gen_ratings
import harness

sys.path.insert(0, os.path.join(harness.HERE, "reference"))
import als_reference  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--dataset", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    rehearse = bool(os.environ.get("BENCH_REHEARSE"))
    spec = harness.load_cell(args.workload)
    harness.device_gate(spec["cell"]["chips"], rehearse)
    config, traffic = spec["config"], spec["traffic"]
    shape = config["data"]
    if rehearse:
        shape = gen_ratings.scaled_shape(shape, traffic["rehearse_cut"])
    import jax
    import jax.numpy as jnp
    from child_train import cached_structure

    t0 = time.time()
    work = harness.work_dir(args.workload)
    u, i, r = gen_ratings.make_ratings(
        cached_structure(shape), shape, args.seed, args.dataset)
    nu, ni = shape["n_users"], shape["n_items"]
    R2_full = jax.device_put(als_reference.dense_half_stars(u, i, r, nu, ni))
    ep = config["engine_params"]
    U0, V0 = als_reference.init_factors(ep["seed"], nu, ni, ep["rank"])
    blocks = traffic["reference_blocks"]
    kw = dict(user_block=blocks["users"], item_block=blocks["items"])
    ref_U, ref_V = als_reference.train(
        R2_full, U0, V0, ep["numIterations"], ep["lambda"], **kw)
    got = np.load(os.path.join(work, "checked_job.npz"))
    # the program numbers its rows by its vocabulary; put them in the
    # benchmark's label order before anything is compared
    prog_U = got["U"][got["user_order"]]
    prog_V = got["V"][got["item_order"]]
    out = {}

    def numbers(pU, pV):
        err = als_reference.prediction_errors(
            R2_full, jnp.asarray(pU), jnp.asarray(pV), ref_U, ref_V)
        return compare.training_numbers(
            pU, pV, np.asarray(ref_U), np.asarray(ref_V), np.asarray(err))

    out["program"] = numbers(prog_U, prog_V)
    if args.control:
        cU, cV = als_reference.train(
            R2_full, U0, V0, ep["numIterations"], ep["lambda"],
            precision="bfloat16", **kw)
        out["control"] = numbers(np.asarray(cU), np.asarray(cV))
        # the faults a training cell can have, planted in the reference
        # put in the program's place: every second rating left out, and
        # the factors handed back as they started
        del cU, cV
        R2_half = jax.device_put(als_reference.dense_half_stars(
            u[::2], i[::2], r[::2], nu, ni))
        fU, fV = als_reference.train(
            R2_half, U0, V0, ep["numIterations"], ep["lambda"], **kw)
        del R2_half
        out["fault_half_batch"] = numbers(np.asarray(fU), np.asarray(fV))
        out["fault_unchanged_state"] = numbers(
            np.asarray(U0), np.asarray(V0))
    out["reference_s"] = time.time() - t0
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
