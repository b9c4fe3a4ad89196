#!/usr/bin/env python3
"""The control of the check that decides ``correct``: the numpy
reference computed from bfloat16 inputs, put in the program's place.

    python3 bench/control.py --config pig_pigmix_4m --traffic serial \
        --seeds 11 12 13 [--rows N]

For each seed it generates the cell's data, takes the control's answer
to every template of the mix, and runs the same comparison as a
benchmark run against the float64 reference.  It prints the compared
numbers and whether the run would be correct; a sound control reads
``correct: false``.  Needs no chip: the control is host numpy.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def readings(config: dict, templates, seed: int) -> dict:
    """The control's compared numbers on ``seed``, with the verdict."""
    import datagen
    from harness import check_answers
    from reference import Reference
    host = datagen.tables(config, seed)
    control = Reference(host, inputs="bfloat16")
    answers = {t: [(control.answer(t), 0)] for t in templates}
    checks, _ = check_answers(answers, host, config["limits"])
    return {"seed": seed, "checks": checks,
            "correct": all(c["value"] <= c["limit"]
                           for c in checks.values())}


def main(argv=None) -> int:
    import harness
    from loadgen import load_mix
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int, default=None,
                    help="page_views rows (default: the configuration's)")
    args = ap.parse_args(argv)
    config = harness.config_of(harness.benchmark(), args.config)
    if args.rows:
        config = dict(config, page_views_rows=args.rows)
    mix = load_mix(harness.mix_path(args.traffic))
    for seed in args.seeds:
        print(json.dumps(readings(config, mix["templates"], seed)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
