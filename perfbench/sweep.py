"""Find a serving cell's knee, once, on the chip.

    python -m perfbench.sweep --workload <cell> --rates 0.8,1.0,1.2,1.4 --seconds 45

One engine, one process; the cell's traffic is offered at each multiple of
its rate in turn, every time through the warm-up and a window.  A
rate is sustained when the waiting line at the end of the window is no
longer than at its middle and no request missed its limits.  One JSON line
per rate; the knee goes into the traffic file and PERF.md by hand.
"""

from __future__ import annotations

import argparse
import json

from perfbench import run as runner
from perfbench import stats, traffic


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="multiples of the traffic file's rate")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    args.trace = 0
    _, cell, ctx = runner.prepare(args)
    ctx["notes"] = True         # the waiting line and the gap histogram
    from perfbench.jobs import serve
    served = serve.Served(ctx)
    try:
        for scale in (float(x) for x in args.rates.split(",")):
            f = served.measure(ctx, scale)
            n = f["notes"]
            print(json.dumps({
                "rate_rps": traffic.rate_rps(cell["traffic_file"], scale),
                "out_tokens_per_s": f["out_tokens"] / f["window_s"],
                "itl_p50_ms": stats.percentile(f["itl_ms"], 50),
                "itl_p95_ms": stats.percentile(f["itl_ms"], 95),
                "ttft_p50_ms": stats.percentile(f["ttft_ms"], 50),
                "ttft_p95_ms": stats.percentile(f["ttft_ms"], 95),
                "occupancy_pct": stats.mean(f["occupancy_pct"]),
                "decode_step_ms": stats.percentile(f["decode_step_ms"], 50),
                "waiting_mid": n["waiting_mid"],
                "waiting_end": n["waiting_end"],
                "attempted": f["attempted"], "failed": f["failed"],
                "preemptions": f["preemptions"],
                "itl_histogram_10ms": n["itl_histogram_10ms"],
            }), flush=True)
    finally:
        served.close()


if __name__ == "__main__":
    main()
