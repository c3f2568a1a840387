"""Which programs a traced run of a cell ran on the device, counted by name:
the Modules line of the device trace that `run.py --trace 1` reduces
(`device_trace.reduce`), beside the number of `chipbench:async_save`
annotations in the window, so that a count can be read per save.

    python chipbench/probe_save_modules.py --out <file> -- <run.py's arguments>

`run.py` runs as it does for a cell and prints its result line; this keeps
only what the reduction saw. A name loses the instance suffix the trace gives
it (`jit_slice(12)` counts as `jit_slice`). Nothing here is compared or part
of any run of a cell.
"""

import collections
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as bench

SAVE = "chipbench:async_save"


def main() -> None:
    argv = sys.argv[1:]
    if "--out" not in argv or "--" not in argv:
        sys.exit(__doc__.split("\n\n")[1])
    out = argv[argv.index("--out") + 1]
    reduce = bench.trace.reduce

    def keep(planes, chips):
        reduced = reduce(planes, chips)
        if reduced:
            count, seconds = collections.Counter(), collections.Counter()
            for name, a, b in reduced["modules"]:
                name = name.split("(")[0]
                count[name] += 1
                seconds[name] += b - a
            saves = sum(name == SAVE for name, _, _ in reduced["annotations"])
            os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
            with open(out, "w") as f:
                json.dump({"window_s": reduced["window_s"], "saves": saves,
                           "modules": dict(count.most_common()),
                           "module_s": dict(seconds.most_common())}, f, indent=1)
        return reduced

    bench.trace.reduce = keep
    bench.main(argv[argv.index("--") + 1:])


if __name__ == "__main__":
    main()
