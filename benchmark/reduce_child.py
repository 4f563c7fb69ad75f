"""Reads one .xplane.pb with jax.profiler (on the CPU backend, never the
chip) and writes reduce.summarize_trace's summary as JSON: the parent of
a serving cell stays off jax."""

import json
import sys

import reduce


def main(path, out):
    trace = reduce.read_xplane(path)
    with open(out, "w") as f:
        json.dump({"summary": reduce.summarize_trace(
            trace, unnamed_gap="between_flushes")}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
