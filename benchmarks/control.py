"""The control of `correct`: the plain reference put in the program's
place and computed in the nearest precision below the configuration's
(bfloat16 for its float32), at the cell's own size. Its answers go
through the same comparison a run's answers go through and have to come
out as not correct: at least one number over its limit.

  python benchmarks/control.py --workload <cell> --seeds 1 2 3 [--frames N]

Prints one JSON line per seed: the numbers compared, their limits, and
which of them the control fails. Needs no chip and is not part of a run;
`tests/benchmark` keeps it at toy size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def control_numbers(config: dict, size: dict, seed: int, n_frames: int,
                    precision: str = "bf16") -> dict:
    """The comparison's numbers for answers computed at `precision`."""
    from benchmarks.harness import manifest

    ref = manifest.reference_of(config)
    gen = manifest.generator_of(config)
    names = gen.key_names(size)
    last_pane = gen.pane_of(size, n_frames - 1)
    width = size["size_ms"] // size["advance_ms"]
    if width == 1:
        panes = {last_pane - 1, last_pane}
        answers = ref.answers(size, seed, n_frames, panes, precision)
    else:
        answers = ref.answers(size, seed, n_frames, precision)
        panes = set(answers)
    served = {"final": ref.rows_from(size, names, answers),
              "complete": sorted(panes), "pulls": [],
              "horizon": gen.pulls(size, n_frames)["horizon"]}
    return ref.compare(size, seed, n_frames, served)


def main(argv=None) -> int:
    from benchmarks.harness import manifest

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--frames", type=int, default=None,
                    help="measured frames (default: four panes' worth)")
    ap.add_argument("--precision", default="bf16")
    ap.add_argument("--dry", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = manifest.cell(args.workload)
    size = manifest.size_of(cell["config"], bool(args.dry))
    gen = manifest.generator_of(cell["config"])
    measured = args.frames or 4 * gen.frames_per_pane(size)
    n_frames = gen.warm_frames(size) + measured
    failed_all = True
    for seed in args.seeds:
        numbers = control_numbers(cell["config"], size, seed, n_frames,
                                  args.precision)
        over = [k for k, v in numbers.items() if v > size["limits"][k]]
        failed_all = failed_all and bool(over)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "precision": args.precision, "frames": n_frames,
                          "numbers": numbers,
                          "limits": {k: size["limits"][k] for k in numbers},
                          "over_limit": over, "correct": not over}),
              flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
