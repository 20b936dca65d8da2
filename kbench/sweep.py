#!/usr/bin/env python3
"""The knee sweep: the builder's tool, not the driver's command.

Starts the cell's server once and offers the cell's mix at each rate
of a ladder for ``--seconds`` (a closed-loop mix has no rate: it is
run once).  For each rate it prints what decides whether the rate is
sustained: requests shed or failed, the queue the server reports at
the end of the window, how long after the window the last token came,
and the median time to first token in the window's first and last
third (a backlog that grows shows as a last third far above the
first).  The knee is the highest rate with nothing shed, an empty
queue at the end and a flat time to first token; the cell then runs at
0.8 of it.  The table goes to ``kbench/out/sweep_<cell>.json``; the
builder writes the rate chosen, with the table, into
``kbench/cells/<cell>.json``.

    python kbench/sweep.py --workload <cell> --rates 0.2,0.3,0.4,0.5 --seconds 51
"""

import argparse
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import clientstats                                   # noqa: E402
import run as bench                                  # noqa: E402
from kserver import BenchError, Server, build_native, log   # noqa: E402
from manifest import Manifest                        # noqa: E402
from paths import MANIFEST, OUT                      # noqa: E402
from tokenizer_gen import tokenizer_dir              # noqa: E402
from trafficgen import schedule                      # noqa: E402


def thirds(result: dict) -> list:
    """Median time to first token (ms) of the requests due in each third
    of the window."""
    out = []
    for k in range(3):
        lo, hi = (result["seconds"] * k / 3, result["seconds"] * (k + 1) / 3)
        vals = [(r["chunk_s"][0] - r["due_s"]) * 1e3 for r in result["requests"]
                if r["chunk_s"] and lo <= r["due_s"] < hi]
        out.append(clientstats.percentile(vals, 50))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="0",
                    help="comma-separated requests per second, rising")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--max-num-seqs", type=int, default=0,
                    help="override the configuration's decode slots")
    ap.add_argument("--manifest", default=MANIFEST)
    ap.add_argument("--expect-platform", default="tpu", choices=("tpu", "cpu"))
    args = ap.parse_args()

    m = Manifest(args.manifest)
    cell = m.cell(args.workload)
    cfg = m.config(cell["config"])
    mix = m.traffic(cell["traffic"])
    vocab = int(cfg["config"]["vocab_size"])
    work_dir = os.path.join(OUT, "sweep_" + cell["name"])
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    if args.max_num_seqs:
        cfg["server"]["config_file"]["max_num_seqs"] = args.max_num_seqs
    config_path = os.path.join(work_dir, "config.json")
    with open(config_path, "w") as f:
        json.dump(cfg, f)
    slots = int(cfg["server"]["config_file"].get("max_num_seqs", 8))
    concurrency = bench.clients_of(cfg, mix)
    rates = [float(r) for r in args.rates.split(",")]
    build_native()
    rows = []
    try:
        with Server(config_path=config_path, name=cfg["name"],
                    tokenizer_dir=tokenizer_dir(vocab),
                    weight_seed=args.seed, work_dir=work_dir) as srv:
            health = srv.wait_healthy(args.expect_platform)
            log(f"healthy after {time.monotonic() - srv.t_launch:.1f}s; "
                f"hbm_sizing {health.get('hbm_sizing')}")
            bench.warm_up(srv, cfg["name"], mix, vocab, concurrency, work_dir)
            for i, rate in enumerate(rates):
                reqs = schedule(mix, seed=args.seed + i, vocab=vocab,
                                seconds=args.seconds, rate_rps=rate,
                                count=int(mix.get("count", 0)))
                before = srv.metrics()
                at_end = {}

                def during(proc, t0=time.monotonic()):
                    while proc.poll() is None:
                        if time.monotonic() - t0 >= args.seconds and not at_end:
                            at_end.update(srv.metrics())
                        time.sleep(0.2)

                res = bench.run_loadgen(
                    bench.make_plan(srv, cfg["name"], mix, reqs, args.seconds,
                                    concurrency), work_dir, f"rate{i}", during)
                after = srv.metrics()
                st = clientstats.reduce(res)
                row = {
                    "rate_rps": rate, "attempted": st["attempted"],
                    "failed": st["failed"], "failures": st["failures"][:3],
                    "shed": after.get("kaito:request_rejected_total", 0)
                    - before.get("kaito:request_rejected_total", 0),
                    "preemptions": after.get("kaito:num_preemptions_total", 0)
                    - before.get("kaito:num_preemptions_total", 0),
                    "waiting_at_end": at_end.get("kaito:num_requests_waiting"),
                    "running_at_end": at_end.get("kaito:num_requests_running"),
                    "kv_usage_at_end": at_end.get("kaito:kv_cache_usage_perc"),
                    "last_done_after_window_s": st["last_done_s"] - args.seconds,
                    "ttft_p50_ms_by_third": thirds(res),
                    "ttft_p50_ms": st["ttft_p50_ms"],
                    "ttft_p95_ms": st["ttft_p95_ms"],
                    "itl_p50_ms": st["itl_p50_ms"],
                    "itl_p95_ms": st["itl_p95_ms"],
                    "out_tok_s": st["out_tok_s"],
                    "gen_late_p95_ms": st["gen_late_p95_ms"],
                    "step_ms": (after["kaito:engine_step_seconds_sum"]
                                - before["kaito:engine_step_seconds_sum"]) * 1e3
                    / max(1.0, after["kaito:engine_step_seconds_count"]
                          - before["kaito:engine_step_seconds_count"]),
                }
                rows.append(row)
                print(json.dumps(row), flush=True)
            health = srv.health()
            srv.stop()
    except BenchError as e:
        log(f"FAILED: {e}")
        return 1
    with open(os.path.join(OUT, f"sweep_{cell['name']}.json"), "w") as f:
        json.dump({"cell": cell["name"], "seconds": args.seconds,
                   "max_num_seqs": slots, "hbm_sizing": health.get("hbm_sizing"),
                   "devices": health["devices"], "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
