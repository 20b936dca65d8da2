#!/usr/bin/env python3
"""One run of one cell: the real server on the chip, driven over HTTP.

    python kbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX: a chip belongs to one process at a
time, and that process is the server child (then, after it has left,
the reference child).  The steps: read the cell, its configuration and
its traffic mix by name; build the word-level tokenizer once per
checkout; start ``launch_server.py``; wait for ``/health`` and refuse
the wrong platform; warm up the shapes the mix uses and send the check
requests (all of that is ``setup_s``); scrape ``/metrics``, run the
load generator for ``--seconds``, drain, scrape again; with
``--trace 1`` bracket the mix's ``trace_seconds`` of the window with
the server's profiler (``trace_window``); stop the server (exit 0
required); run or look up the plain reference the configuration names
for the check requests and compare.  The last line of
stdout is the result object; everything else goes to stderr or
``kbench/out/<cell>/``.
"""

import argparse
import importlib
import json
import os
import shutil
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check                                            # noqa: E402
import clientstats                                      # noqa: E402
import trace_spans                                      # noqa: E402
from kserver import (BenchError, Server, build_native,  # noqa: E402
                     child_env, log, stop_child)
from manifest import Manifest, load_json                # noqa: E402
from paths import KBENCH, MANIFEST, OUT, ROOT           # noqa: E402
from tokenizer_gen import tokenizer_dir                 # noqa: E402
from trafficgen import schedule, words                  # noqa: E402

WARM_SEED = 0x3A97          # warm-up traffic is the same in every run
POLL_PERIOD_S = 0.5
# /stop_profile returns when the trace is written, while the server goes
# on serving: the export's seconds follow the file's size, 1.3-2.4 s a MB
# by cell and host over the traced runs of PRs 50-55 (PERF.md section 7), and a mix's
# trace_seconds is sized so that its largest cell's stop fits this limit
PROFILER_TIMEOUT_S = 300.0
# the slowest export on record (phi4mini-batch's parent run of PR 51's call
# a: 68.6 MB in 162.5 s, 2.37 s a MB) and the share of the limit a mix's
# span may cost at that rate (tests/kbench/test_kbench_trace_window.py
# holds every file under traffic/ to it)
EXPORT_S_PER_MB = 2.4
TRACE_BUDGET_SHARE = 0.75
STOP_WARN_SHARE = 2 / 3     # a stop over this share of the limit is said
LATE_SHARE = 0.10           # generator lateness worth a warning, of the mean gap


def run_loadgen(plan: dict, work_dir: str, tag: str, during=None) -> dict:
    """The load generator as a process of its own; ``during(proc)`` runs
    in this one while it works."""
    plan_path = os.path.join(work_dir, f"{tag}_plan.json")
    out_path = os.path.join(work_dir, f"{tag}_result.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(KBENCH, "loadgen.py"), plan_path,
         out_path], cwd=ROOT, start_new_session=True)
    try:
        if during is not None:
            during(proc)
        proc.wait(timeout=plan["seconds"] + plan["drain_timeout_s"] + 60)
    finally:
        rc = stop_child(proc, grace_s=5.0)
    if rc != 0:
        raise BenchError(f"load generator ({tag}) exited {rc}")
    return load_json(out_path)


def make_plan(srv: Server, model: str, mix: dict, reqs: list,
              seconds: float, concurrency: int) -> dict:
    return {"host": "127.0.0.1", "port": srv.port, "model": model,
            "loop": mix["loop"], "concurrency": concurrency,
            "seconds": seconds,
            "drain_timeout_s": float(mix.get("drain_timeout_s", 120)),
            "requests": [{"due_s": r["due_s"], "max_tokens": r["max_tokens"],
                          "prompt": words(r["prompt_ids"])} for r in reqs]}


def warm_up(srv, model, mix, vocab, concurrency, work_dir) -> int:
    """Every shape the window will use, before it: the mix itself,
    replayed from a fixed seed with short outputs at the warm-up's
    own rate.  Returns the tokens generated."""
    w = mix["warmup"]
    reqs = schedule(mix, seed=WARM_SEED, vocab=vocab, seconds=w["seconds"],
                    rate_rps=w.get("rate_rps", 0.0), count=w.get("count", 0))
    for r in reqs:
        r["max_tokens"] = min(r["max_tokens"], w["max_tokens"])
    plan = make_plan(srv, model, mix, reqs, w["seconds"], concurrency)
    plan["drain_timeout_s"] = 900.0     # a cold compile cache is slow, not wrong
    res = run_loadgen(plan, work_dir, "warmup")
    stats = clientstats.reduce(res)
    if stats["failed"]:
        raise BenchError(f"warm-up: {stats['failed']} of "
                         f"{stats['attempted']} failed: {stats['failures']}")
    return stats["tokens"]


def clients_of(cfg: dict, mix: dict) -> int:
    """A closed-loop mix's clients: so many per decode slot."""
    slots = int(cfg["server"].get("config_file", {}).get("max_num_seqs", 8))
    return int(mix.get("concurrency_per_slot", 0) * slots)


def health_problems(health: dict, expect: dict, chips: int) -> list:
    bad = []
    got = {"attention": health.get("attention"),
           "prefix_cache": health.get("prefix_cache"),
           "hbm_sizing_source": (health.get("hbm_sizing") or {}).get("source")}
    for key, want in expect.items():
        if got.get(key) != want:
            bad.append(f"/health {key} is {got.get(key)!r}, expected {want!r}")
    if health.get("device_count", 0) < chips:
        bad.append(f"{health.get('device_count')} devices, the cell "
                   f"needs {chips}")
    for d in health.get("devices", []):
        if d.get("bytes_limit") and d["peak_bytes_in_use"] > d["bytes_limit"]:
            bad.append(f"device {d['id']} peaked at {d['peak_bytes_in_use']} "
                       f"of {d['bytes_limit']} bytes")
    return bad


def trace_window(post, begin_s: float, span_s: float, window_s: float,
                 now=time.monotonic, sleep=time.sleep) -> dict:
    """Bracket ``span_s`` seconds of the window with the server's
    profiler.  ``post(path)`` returns the HTTP status; times are
    seconds on ``now``'s clock, the window starting at its value at
    the call.  The stop is counted from the *return* of
    ``/start_profile`` (the profiler's start can hold the server for
    seconds), so the trace is ``span_s`` long or the run fails: a start
    that returns too late for the span to end inside the window is
    stopped at once and named, never left as a short trace."""
    t0 = now()
    sleep(begin_s)
    asked = now()
    status = post("/start_profile")
    started = now()
    if status != 200:
        raise BenchError(f"/start_profile answered {status}")
    late = started - t0 + span_s - window_s
    if late <= 0:
        sleep(span_s - (now() - started))
    stopping = now()
    status = post("/stop_profile")
    stopped = now()
    if late > 0:
        raise BenchError(
            f"/start_profile, asked at {asked - t0:.1f}s, returned at "
            f"{started - t0:.1f}s: {span_s:g}s of trace from there would "
            f"end {late:.1f}s after the {window_s:.0f}s window")
    if status != 200:
        raise BenchError(f"/stop_profile answered {status}")
    return {"start_s": started - t0, "stop_s": stopping - t0,
            "start_took_s": started - asked, "stop_took_s": stopped - stopping}


def trace_middle(post, seconds: float, span_s: float, traced: dict,
                 **clock) -> None:
    """The tracer thread's work: ``span_s`` in the middle of the window,
    its times into ``traced``; a bracket that failed is left there under
    ``error`` for ``run`` to raise (a stop that outlasts
    ``PROFILER_TIMEOUT_S`` is ``Server.request``'s ``TimeoutError``).
    ``loadgen.py`` is starting up meanwhile, so its clock's zero is read
    from its result afterwards."""
    traced["t0_unix"] = time.time()
    try:
        traced.update(trace_window(post, max(0.0, (seconds - span_s) / 2),
                                   span_s, seconds, **clock))
    except (BenchError, OSError) as e:
        traced["error"] = f"the profiler's bracket failed: {e}"


def stop_warning(cell: str, cost: dict, span_s: float) -> str:
    """What a traced run says of a stop that came near the limit, before
    a faster cell's longer export fails there; empty up to
    ``STOP_WARN_SHARE`` of it."""
    share = cost["stop_profile_s"] / PROFILER_TIMEOUT_S
    if share <= STOP_WARN_SHARE:
        return ""
    return (f"WARNING: {cell}: /stop_profile took "
            f"{cost['stop_profile_s']:.1f}s, {share:.0%} of the "
            f"{PROFILER_TIMEOUT_S:.0f}s it may, for "
            f"{cost['xplane_bytes'] / 1e6:.1f} MB and the mix's trace_seconds "
            f"{span_s:g}: the span wants shortening in a benchmark PR "
            "(kbench/README.md, the trace's budget)")


def reduce_trace(profile_dir: str, work_dir: str, into: dict) -> None:
    """Both reductions of the run's trace, each in a child held to the
    CPU, into ``into``: ``trace`` (``trace_reduce.py``), ``spans``
    (``trace_spans.py``, which the readers then find reduced), ``cost``,
    or ``error``.  Runs in a thread beside the reference child: the two
    take 20 s on a 14 s trace, and need no chip."""
    try:
        path = trace_spans.newest_trace(profile_dir)
        if path is None:
            raise BenchError("the profiler wrote no trace")
        t0 = time.monotonic()
        out = os.path.join(work_dir, "trace.json")
        with open(out, "w") as f:
            res = subprocess.run(
                [sys.executable, os.path.join(KBENCH, "trace_reduce.py"), path],
                stdout=f, env=child_env({"JAX_PLATFORMS": "cpu"}), cwd=ROOT)
        if res.returncode != 0:
            raise BenchError("trace reduction failed")
        into["trace"] = load_json(out)
        t1 = time.monotonic()
        into["spans"] = trace_spans.reduced_newest(into)
        into["cost"] = {"xplane_bytes": os.path.getsize(path),
                        "trace_reduce_s": t1 - t0,
                        "trace_spans_s": time.monotonic() - t1}
    except BenchError as e:
        into["error"] = str(e)


def peaks_for(device_kind: str) -> dict:
    """The published peaks of the device JAX names; a device that is
    not in the table is an error, never a default."""
    peaks = load_json(os.path.join(KBENCH, "peaks.json"))
    if device_kind not in peaks or device_kind.startswith("_"):
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         "kbench/peaks.json")
    return peaks[device_kind]


def layer_metrics(m: Manifest, cell: str, ctx: dict) -> dict:
    out = {}
    for entry in m.metrics_for(cell, "per_layer"):
        spec = m.layer_metric(entry["name"])
        reader = importlib.import_module("readers." + spec["reader"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:           # nothing to read: leave it out
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=MANIFEST)
    ap.add_argument("--expect-platform", default="tpu", choices=("tpu", "cpu"),
                    help="cpu exists for the rehearsal only")
    ap.add_argument("--perturb-reference", default="",
                    help="a deliberately cruder reference, to show that "
                         "the tolerance catches one")
    args = ap.parse_args()
    t_start = time.monotonic()

    if not os.path.isdir(os.path.join(ROOT, "kaito_tpu")):
        log("no kaito_tpu package beside kbench/: nothing to measure")
        return 1
    try:
        return run(args, t_start)
    except BenchError as e:
        log(f"FAILED: {e}")
        return 1


def run(args, t_start: float) -> int:
    m = Manifest(args.manifest)
    cell = m.cell(args.workload)
    cfg = m.config(cell["config"])
    mix = m.traffic(cell["traffic"])
    settings = m.cell_settings(cell["name"])
    vocab = int(cfg["config"]["vocab_size"])
    model = cfg["name"]
    on_cpu = args.expect_platform == "cpu"
    expect = cfg["server"]["expect_cpu" if on_cpu else "expect"]
    weight_seed = args.seed % (2 ** 31 - 1)
    rate = float(settings.get("rate_rps", 0.0))
    concurrency = clients_of(cfg, mix)
    span = float(mix.get("trace_seconds", 3.0))     # of the window, traced
    chips = 1 if on_cpu else cell["chips"]
    if mix["loop"] == "open" and rate <= 0:
        raise BenchError(f"cell {cell['name']} has no rate: run the sweep "
                         f"and write kbench/cells/{cell['name']}.json")

    work_dir = os.path.join(OUT, cell["name"])
    shutil.rmtree(work_dir, ignore_errors=True)
    profile_dir = os.path.join(work_dir, "profile")
    os.makedirs(profile_dir)
    build_native()
    tok_dir = tokenizer_dir(vocab)
    t_built = time.monotonic()
    prompts = check.check_prompts(mix, args.seed, vocab)
    n_decode = int(mix["check"]["decode_tokens"])
    reqs = schedule(mix, seed=args.seed, vocab=vocab, seconds=args.seconds,
                    rate_rps=rate, count=int(mix.get("count", 0)))

    polls, traced, client_tokens, problems = [], {}, 0, []
    with Server(config_path=m.config_path(model), name=model,
                tokenizer_dir=tok_dir, weight_seed=weight_seed,
                work_dir=work_dir,
                env={"KAITO_PROFILE_DIR": profile_dir}) as srv:
        health = srv.wait_healthy(args.expect_platform)
        t_healthy = time.monotonic()
        log(f"healthy after {t_healthy - srv.t_launch:.1f}s on "
            f"{health['device_count']} x {health['device_kind']}")
        problems += health_problems(health, expect, chips)
        first = srv.metrics()
        client_tokens += warm_up(srv, model, mix, vocab, concurrency, work_dir)
        t_warm = time.monotonic()
        sent = check.send_checks(srv, model, prompts, n_decode)
        client_tokens += sent["tokens"]
        setup_s = time.monotonic() - t_start
        log(f"set-up {setup_s:.1f}s: native library and tokenizer "
            f"{t_built - t_start:.1f}, launch to healthy "
            f"{t_healthy - t_built:.1f}, warm-up {t_warm - t_healthy:.1f}, "
            f"check requests {t_start + setup_s - t_warm:.1f}")

        before = srv.metrics()

        def profiler(path):
            return srv.request(path, {}, PROFILER_TIMEOUT_S)[0]

        def during(proc):
            t0 = time.monotonic()
            thread = None
            if args.trace and not on_cpu:
                # a CPU has no device plane to trace: the rehearsal takes none
                thread = threading.Thread(
                    target=trace_middle, daemon=True,
                    args=(profiler, args.seconds, span, traced))
                thread.start()
            while proc.poll() is None and args.trace:
                if time.monotonic() - t0 < args.seconds:
                    polls.append(srv.metrics())
                time.sleep(POLL_PERIOD_S)
            if thread is not None:      # the stop outlasts the window
                thread.join(timeout=args.seconds + 2 * PROFILER_TIMEOUT_S)
                if thread.is_alive():
                    traced["error"] = "the profiler's bracket did not return"

        result = run_loadgen(make_plan(srv, model, mix, reqs, args.seconds,
                                       concurrency), work_dir, "window", during)
        if "error" in traced:
            raise BenchError(traced["error"])
        stats = clientstats.reduce(result)
        client_tokens += stats["tokens"]
        for _ in range(20):      # the handler counts a request after its last chunk
            after = srv.metrics()
            if (after.get("kaito:generation_tokens_total", 0)
                    - first.get("kaito:generation_tokens_total", 0)
                    >= client_tokens):
                break
            time.sleep(0.1)
        health_after = srv.health()
        srv.stop()

    reduced, reducing = {}, None
    if args.trace and not on_cpu:
        reducing = threading.Thread(target=reduce_trace,
                                    args=(profile_dir, work_dir, reduced))
        reducing.start()

    # ---- accounting: counts that repeat exactly -----------------------
    problems += health_problems(health_after, expect, chips)
    if stats["failed"]:
        problems.append(f"{stats['failed']} of {stats['attempted']} requests "
                        f"failed: {stats['failures']}")
    for name in ("kaito:requests_failed_total", "kaito:engine_fatal_total"):
        if after.get(name, 0.0) != first.get(name, 0.0):
            problems.append(f"{name} moved to {after.get(name)}")
    served_tokens = (after.get("kaito:generation_tokens_total", 0.0)
                     - first.get("kaito:generation_tokens_total", 0.0))
    if served_tokens != client_tokens:
        problems.append(f"server counted {served_tokens:.0f} generated tokens, "
                        f"the client {client_tokens}")

    # ---- numerical: logprobs against the plain reference --------------
    ref = check.expectations(
        cfg, weight_seed, check.reference_requests(prompts, sent["served"]),
        platform=args.expect_platform, work_dir=work_dir,
        perturb=args.perturb_reference)
    verdict = check.compare(prompts, sent["served"], ref,
                            float(cfg["tolerance"]["logprob_abs"]))
    log("check errors " + json.dumps(verdict["worst"])
        + f" against tolerance {verdict['tolerance']}")
    problems += [f"numerical check, clause {c}: error "
                 f"{verdict['worst'][c]:.4f} > {verdict['tolerance']}"
                 for c in verdict["failed"]]
    for p in problems:
        log("INCORRECT: " + p)

    # ---- what the run reports ----------------------------------------
    gap_ms = 1e3 / rate if rate else 0.0
    log(f"samples {json.dumps(stats['samples'])}; attempted "
        f"{stats['attempted']}, failed {stats['failed']}; gen_late_p95_ms "
        f"{stats['gen_late_p95_ms']:.3f}; last chunk at "
        f"{stats['last_done_s']:.1f}s of a {args.seconds:.0f}s window")
    if gap_ms and stats["gen_late_p95_ms"] > LATE_SHARE * gap_ms:
        log(f"WARNING: the generator ran late by more than "
            f"{LATE_SHARE:.0%} of the mean gap ({gap_ms:.1f} ms)")
    peak = max((d.get("peak_bytes_in_use") or 0
                for d in health_after["devices"]), default=0)
    device = {"platform": health["platform"], "kind": health["device_kind"],
              "count": health["device_count"], "memory_peak_bytes": peak}
    out = {"correct": not problems, "attempted": stats["attempted"],
           "failed": stats["failed"]}
    client = dict(stats, setup_s=setup_s)
    trace = cost = None
    if args.trace:
        if reducing is not None:
            reducing.join()
            if "error" in reduced:
                raise BenchError(reduced["error"])
            trace = reduced["trace"]
            if trace["busy_s"] <= 0:
                raise BenchError("the traced run saw no operation on the device")
            cost = dict(reduced["cost"], start_profile_s=traced["start_took_s"],
                        stop_profile_s=traced["stop_took_s"])
            log("the trace cost " + json.dumps(cost))
            near = stop_warning(cell["name"], cost, span)
            if near:
                log(near)
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            out["breakdown"] = {
                "device_ops": [[n, s] for n, s in sorted(
                    trace["ops"].items(), key=lambda x: -x[1])[:10]],
                "idle_gaps": reduced["spans"]["idle_by_name"]}
        log("device time by program " + json.dumps(
            (trace or {}).get("modules", {})))
        # the traced span on the load generator's clock: from the return
        # of /start_profile to the sending of /stop_profile
        on_gen = traced.get("t0_unix", 0.0) - result["t0_unix"]
        ctx = {"before": before, "after": after, "polls": polls,
               "client": client, "trace": trace, "config": cfg, "mix": mix,
               # the window's raw requests with their prompt lengths
               "requests": [dict(r, prompt_tokens=len(reqs[r["idx"]]["prompt_ids"]))
                            for r in result["requests"]],
               "traced_s": ([on_gen + traced["start_s"],
                             on_gen + traced["stop_s"]] if trace else []),
               "health": health_after,
               "peaks": None if on_cpu else peaks_for(health["device_kind"])}
        out["metrics"] = layer_metrics(m, cell["name"], ctx)
    else:
        out["metrics"] = {}
        for entry in m.metrics_for(cell["name"], "end_to_end"):
            value = client.get(entry["name"])
            if value is None:
                raise BenchError(f"{entry['name']}: too few samples "
                                 f"({stats['samples']}) for this tail")
            out["metrics"][entry["name"]] = {"value": value,
                                             "unit": entry["unit"]}
    out["device"] = device
    with open(os.path.join(work_dir, "report.json"), "w") as f:
        json.dump({"args": vars(args), "result": out, "client": client,
                   "check": verdict, "served": sent["served"],
                   "trace_cost": cost,
                   "metrics_before": before, "metrics_after": after,
                   "problems": problems, "health": health_after}, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
