#!/usr/bin/env python3
"""The control of the ``correct`` comparison: the configuration's reference
module (``bench/reference.py`` where it names none), put in the program's
place and computed one precision below the configuration's float32
(bfloat16 image, kernels and intermediates, float32 sums), must fail it.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds s]

For each seed it builds the cell's traffic at the cell's own size, takes
the lanes a run would sample and the whole chunks of their window (a
closed loop: ``--lane-events`` per lane, as a run returns them), and
prints the numbers ``correct`` compares for the control.  No accelerator
is used.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if sys.path and pathlib.Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT)]

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402
from bench.traffic import OpenSchedule  # noqa: E402


def readings(bench: harness.Bench, workload: str, seed: int,
             seconds: float, precision: str = "bfloat16",
             config_override: dict | None = None,
             lane_events: int | None = None) -> dict:
    """The compared numbers of ``precision``'s reference in the program's
    place, against the float64 reference, over the sampled lanes; the
    reference is the module the configuration names."""
    wl = bench.workload(workload)
    config = {**bench.config(wl["config"]), **(config_override or {})}
    ref = bench.reference_of(config)
    cell = bench.cell(workload)
    mix = bench.mix(wl["traffic"])
    src = bench.kind(mix["kind"]).build(mix, cell, config, seed, seconds)
    lanes = int(config["capacity"])
    weight = (np.bincount(src.lane, minlength=lanes)
              if isinstance(src, OpenSchedule) else np.ones(lanes))
    det = ref.Detector.from_config(config)
    worst: dict = {}
    for lane in harness.sample_lanes(weight, int(config["sample_lanes"]),
                                     seed):
        n = (int(np.count_nonzero(src.lane == lane))
             if isinstance(src, OpenSchedule) else int(lane_events))
        xy, ts = harness.lane_stream(src, lane, n)
        seed_of_lane = harness.POOL_SEED + lane
        want = ref.run_lane(xy, ts, det, lane_seed=seed_of_lane)
        low = ref.run_lane(xy, ts, det, precision, lane_seed=seed_of_lane)
        got = ref.compare_lane(
            low.scores, low.keep, ref.lane_state(low, det, precision),
            want, det, config["limits"]["score_gap"])
        for k, v in got.items():
            worst[k] = max(worst.get(k, 0), v)
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--lane-events", type=int, default=None,
                    help="closed loop: events per sampled lane, as a run "
                         "returns them")
    args = ap.parse_args(argv)
    bench = harness.Bench(ROOT)
    seconds = args.seconds or float(bench.spec["run_seconds"])
    limits = bench.config(bench.workload(args.workload)["config"])["limits"]
    for seed in (int(s) for s in args.seeds.split(",")):
        row = {"workload": args.workload, "seed": seed, "limits": limits}
        row["bfloat16"] = readings(bench, args.workload, seed, seconds,
                                   lane_events=args.lane_events)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
