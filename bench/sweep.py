"""Find an open-loop serving cell's knee: the highest offered rate at which
the backlog does not grow over the window.

    python3 bench/sweep.py --workload NAME --rates 4,6,8 [--seconds S]

One process sets the cell up once, then offers each rate in turn, in
ascending order and without draining between them: ``--preroll``
seconds of the first rate bring the slots to a steady state, and each
later rate starts from the load the one before left. It prints one JSON
line per rate: requests completed per second in the window, the queue at
the window's start and end, and the TTFT and TPOT tails of the requests
that finished in it. The knee found is written into the mix's file by hand,
with the cell's rate at about four fifths of it; the benchmark's runs
never search for a rate.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--preroll", type=float, default=60.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.use_compile_cache()
    try:
        devs = harness.require_chips(cell.chips)
    except harness.NoDevice as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    drv = harness.load_module("drivers", "serve")
    import traffic as traffic_mod
    # warm every prefill bucket of the prompt range, since each rate
    # sends a set of lengths of its own
    run = drv.Run(cell, args.seed, 3600.0)
    run.setup()
    run.window_s = args.seconds
    base = dict(cell.traffic)
    preroll = args.preroll
    for rate in sorted(float(x) for x in args.rates.split(",")):
        run.mix = dict(base, rate_per_s=rate, drain_s=0.0, preroll_s=preroll)
        preroll = 0.0
        run.reqs, run.measured, run.lateness = [], [], []
        q = {}
        ticks = []
        eng = run.eng
        t0 = time.monotonic()

        class Probe:          # reads the queue as the window opens and shuts
            def poll(self, t_rel):
                if "start" not in q and t_rel >= 0:
                    q["start"] = len(eng.queue)

            def tick(self):
                t = time.monotonic()
                eng.tick_once()
                ticks.append(time.monotonic() - t)

            def wait(self, s):
                time.sleep(s)
        run.rec = Probe()
        run.open_loop()
        q["end"] = len(eng.queue)
        w0 = run.t_window
        done = [r for r in run.reqs
                if r.done and w0 <= r.t_done < w0 + args.seconds]
        fin = [r for r in run.measured if r.done]
        print(json.dumps({
            "rate_per_s": rate,
            "completed_per_s": len(done) / args.seconds,
            "queue_at_window_start": q.get("start"),
            "queue_at_window_end": q["end"],
            "measured": len(run.measured), "measured_done": len(fin),
            "ttft_p95_s": traffic_mod.percentile(
                [r.t_first - r.t_submit for r in fin], 95),
            "tpot_p95_s": traffic_mod.percentile(
                [(r.t_done - r.t_first) / (len(r.out) - 1) for r in fin], 95),
            "active_slots": sum(s is not None for s in eng.slots),
            "tick_median_s": traffic_mod.percentile(ticks, 50),
            "memory_peak_bytes": harness.device_record(devs)[
                "memory_peak_bytes"],
            "loop_s": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
