#!/usr/bin/env python3
"""Chip smoke: the real server, a real model, a few requests.

The quickest proof that the system still starts on the chip.  This
parent never imports jax (a chip belongs to one process at a time): it
rebuilds the native library, starts ``python -m kaito_tpu.engine.server``
as a child, talks HTTP to it, stops it, and only then starts the next
child.  Legs, in order:

- ``serve``    one chip, phi-4-mini-instruct at full depth and width,
               random weights from the seed.  Five kinds of request
               (see ``run_requests``) cover fresh flash prefill, a
               turn of several prompts, chunked context prefill, fused
               and batched decode, SSE streaming and a prefix-cache hit.
- ``kernels``  ``benchmarks/kernel_bench.py --parity``: every Pallas
               kernel compiled for the chip against its pure-JAX
               reference.
- ``tp``/``dp`` the same requests against ``--tensor-parallel-size N``
               and then ``--data-parallel-size N`` on a host with N > 1
               devices; ``skipped: 1 device`` otherwise.

It passes only on the platform it expects (a TPU unless told otherwise)
and only if nothing failed underneath: every response complete, zero
failed requests, zero engine-fatal steps, token counts exact, the
Pallas attention path, measured HBM sizing, the native prefix cache
with a hit, and a clean child exit.

On success stdout carries two JSON lines: the full report (versions,
legs, timings, memory, compile cache; also written to
``chiprun_out/chip_smoke/report.json``) and, last, the verdict
``{"ok": true, "device": {"platform", "kind", "count"}}`` with the
device as the server's jax reported it.  On failure the report goes to
stderr, stdout stays empty and the exit code is 1.

    python chip_smoke.py                       # on the chip
    python chip_smoke.py --model tiny-llama-test --expect-platform cpu
"""

import argparse
import json
import math
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from importlib import metadata

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
LEGS = ("serve", "kernels", "tp", "dp")
SEED = 21

# what the engine must have selected, by the platform it runs on
EXPECT = {
    "tpu": {"attention": "pallas", "hbm_source": "measured"},
    "cpu": {"attention": "jax", "hbm_source": "seq-cap"},
}
# the kernels on the default bf16 serving path; every other kernel must
# match too, but these two are what the serve leg just ran
MAIN_PATH_KERNELS = ("decode_bf16", "flash_prefill")
# and what the serve leg's model does not run: the decode kernel of a
# model with a state-space mixer beside attention (its state pool's
# rows, empty ones included), and flash prefill at MiMo-V2.5's two
# geometries (16 and 8 query heads stacked on a KV head's key blocks,
# keys wider than values, a window with a sink, whole query blocks of
# padding written as zeros), and the un-sort of its shared expert layer
# at the longest prefill bucket
REQUIRED_KERNELS = MAIN_PATH_KERNELS + (
    "ssm_state_update", "flash_prefill_gqa16", "flash_prefill_window_sink",
    "moe_combine_ep16")


class SmokeError(Exception):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", file=sys.stderr, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


# ---------------------------------------------------------------------
# children
# ---------------------------------------------------------------------

def child_env() -> dict:
    env = dict(os.environ)
    # run from the checkout: the package is not installed
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def stop_child(proc: subprocess.Popen, grace_s: float = 60.0) -> int:
    """SIGTERM, wait, and make sure nothing of the child's group is
    left.  Returns the child's own exit code."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            log(f"child {proc.pid} ignored SIGTERM for {grace_s:.0f}s")
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    return proc.wait()


def build_native() -> None:
    """From what git would commit: the .so is ignored, so build it."""
    res = subprocess.run(
        ["make", "-C", os.path.join(ROOT, "kaito_tpu", "native"),
         "clean", "all"], capture_output=True, text=True)
    check(res.returncode == 0,
          f"native build failed:\n{res.stdout}{res.stderr}")


def cache_dir() -> str:
    """Where enable_compile_cache() puts the children's cache (the
    module imports jax only when it has to set something)."""
    from kaito_tpu.utils.platform import DEFAULT_CACHE_DIR

    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def cache_entries() -> int:
    return sum(len(files) for _, _, files in os.walk(cache_dir()))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """One ``kaito_tpu.engine.server`` child."""

    def __init__(self, leg: str, model: str, extra_args: list):
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(OUT_DIR, f"server_{leg}.log")
        self.cmd = [sys.executable, "-m", "kaito_tpu.engine.server",
                    "--model", model, "--host", "127.0.0.1",
                    "--port", str(self.port)] + extra_args
        self.proc = None
        self.t_launch = 0.0

    def __enter__(self):
        log("starting: " + " ".join(self.cmd[1:]))
        self.t_launch = time.monotonic()
        with open(self.log_path, "w") as out:
            self.proc = subprocess.Popen(
                self.cmd, cwd=ROOT, env=child_env(), stdout=out,
                stderr=subprocess.STDOUT, start_new_session=True)
        return self

    def __exit__(self, *exc):
        if self.proc.poll() is None:    # a failure path: just stop it
            stop_child(self.proc)
        return False

    def request(self, path: str, body=None, timeout: float = 900.0):
        """(status, bytes); connection errors raise OSError."""
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            self.base + path, data=data,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def wait_healthy(self, expect_platform: str,
                     timeout_s: float = 900.0) -> dict:
        """Poll /health until the engine is up.  The loading stub (503)
        already names the platform: the wrong one fails here, before
        the weights load."""
        while time.monotonic() - self.t_launch < timeout_s:
            check(self.proc.poll() is None,
                  f"server exited {self.proc.returncode} while loading:\n"
                  + tail(self.log_path))
            try:
                status, body = self.request("/health", timeout=5.0)
            except OSError:
                status = 0
            if status in (200, 503):
                health = json.loads(body)
                check(health.get("platform") == expect_platform,
                      f"server runs on {health.get('platform')!r}, "
                      f"expected {expect_platform!r}")
                if status == 200:
                    return health
            time.sleep(0.5)
        raise SmokeError(f"server not healthy after {timeout_s:.0f}s:\n"
                         + tail(self.log_path))

    def metrics(self) -> dict:
        """Unlabelled samples of /metrics as {name: value}."""
        status, body = self.request("/metrics", timeout=30.0)
        check(status == 200, f"/metrics answered {status}")
        out = {}
        for line in body.decode().splitlines():
            parts = line.split()
            if len(parts) == 2 and not line.startswith("#"):
                try:
                    out[parts[0]] = float(parts[1])
                except ValueError:
                    pass
        return out

    def stop(self) -> None:
        rc = stop_child(self.proc)
        check(rc == 0, f"server exited {rc} on SIGTERM:\n"
              + tail(self.log_path))


# ---------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------

_WORDS = ("page", "token", "mesh", "shard", "slot", "prefill", "decode",
          "kernel", "cache", "queue", "batch", "chip", "layer", "head",
          "step", "ring", "host", "pool", "scale", "block")


def text(rng: random.Random, n_chars: int) -> str:
    """Fixed text from the seed.  The tokenizer is the byte fallback, so
    characters are tokens (plus one BOS)."""
    out = ""
    while len(out) < n_chars:
        out += rng.choice(_WORDS) + " "
    return out[:n_chars]


def completion(srv: Server, prompt: str, max_tokens: int) -> dict:
    """One greedy /v1/completions, checked for shape: 200, exactly the
    requested completion tokens, one finite logprob per token."""
    status, body = srv.request("/v1/completions", {
        "prompt": prompt, "max_tokens": max_tokens, "temperature": 0,
        "ignore_eos": True, "logprobs": 1})
    check(status == 200, f"completion answered {status}: {body[:400]!r}")
    resp = json.loads(body)
    got = resp["usage"]["completion_tokens"]
    check(got == max_tokens,
          f"asked for {max_tokens} completion tokens, got {got}")
    lps = resp["choices"][0]["logprobs"]["token_logprobs"]
    check(len(lps) == max_tokens and all(
        isinstance(x, (int, float)) and math.isfinite(x) for x in lps),
        f"expected {max_tokens} finite logprobs, got {lps!r}")
    return {"text": resp["choices"][0]["text"], "logprobs": lps}


def chat_stream(srv: Server, content: str, max_tokens: int) -> int:
    """One streamed /v1/chat/completions; returns the SSE event count.
    Token ids above 255 decode to no text under the byte tokenizer, so
    the content deltas do not count tokens — /metrics does, below."""
    status, body = srv.request("/v1/chat/completions", {
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens, "temperature": 0, "ignore_eos": True,
        "stream": True})
    check(status == 200, f"chat stream answered {status}: {body[:400]!r}")
    events = [e[len("data: "):] for e in body.decode().split("\n\n")
              if e.startswith("data: ")]
    check(len(events) >= 3 and events[-1] == "[DONE]",
          f"SSE stream did not end in [DONE]: {events[-3:]!r}")
    first, last = json.loads(events[0]), json.loads(events[-2])
    check(first["choices"][0]["delta"].get("role") == "assistant",
          f"first SSE chunk carries no role: {first!r}")
    check(last["choices"][0]["finish_reason"] == "length",
          f"last SSE chunk did not finish by length: {last!r}")
    return len(events)


def run_requests(srv: Server, replicas: int) -> dict:
    """The five request kinds.  ``replicas`` is the number of engine
    groups behind the server: the repeat of prompt (a) goes out once per
    group (idle groups are picked round-robin), so one copy lands on
    the group whose prefix cache holds it."""
    rng = random.Random(SEED)
    asked = 0
    out = {}

    # (a) one ~128-token prompt, 64 out: fresh flash prefill, fused decode
    prompt_a = text(rng, 120)
    t_sent = time.monotonic()
    first = completion(srv, prompt_a, 64)
    asked += 64
    ttft = srv.metrics().get("kaito:time_to_first_token_seconds_sum", 0.0)
    out["seconds_to_first_token"] = round(t_sent - srv.t_launch + ttft, 1)

    # (b) eight ~100-200-token prompts at once, 64 out each: prefill
    # turns of several prompts, batched decode
    prompts = [text(rng, rng.randint(100, 200)) for _ in range(8)]
    errors = []

    def one(p):
        try:
            completion(srv, p, 64)
        except Exception as e:     # re-raised on the main thread below
            errors.append(e)

    threads = [threading.Thread(target=one, args=(p,)) for p in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    asked += 8 * 64

    # (c) one ~2,000-token prompt, 32 out: chunked context prefill
    completion(srv, text(rng, 1960), 32)
    asked += 32

    # (d) one streamed chat completion
    out["sse_events"] = chat_stream(srv, text(rng, 60), 48)
    asked += 48

    # (e) prompt (a) again: same answer, from the prefix cache.  A hit
    # recomputes the prompt's tail through the context-attention path,
    # so the two runs agree to bf16 resolution, not bitwise: one bf16
    # ulp of a logit near |5| is 0.03, hence 0.1 per logprob.  With
    # random weights the top logits are near ties, so some token down
    # the line may legitimately flip and the texts part there; the
    # FIRST token's logprob cannot move by more than the tolerance even
    # if it flips (a flip needs a tie), so that is what must agree.
    hits0 = srv.metrics().get("kaito:prefix_cache_hits_total", 0.0)
    out["repeat_agrees_for_tokens"] = 64
    for _ in range(replicas):
        again = completion(srv, prompt_a, 64)
        asked += 64
        check(again["text"] == first["text"],
              "prompt (a) repeated gave another text: "
              f"{again['text']!r} != {first['text']!r}")
        drift = [abs(x - y) for x, y in
                 zip(again["logprobs"], first["logprobs"])]
        agree = next((i for i, d in enumerate(drift) if d >= 0.1), 64)
        check(agree >= 1, f"prompt (a) repeated: first-token logprob "
                          f"{again['logprobs'][0]} != "
                          f"{first['logprobs'][0]} (bf16 tolerance 0.1)")
        out["repeat_agrees_for_tokens"] = min(
            out["repeat_agrees_for_tokens"], agree)
    m = srv.metrics()
    out["prefix_cache_hits"] = int(
        m.get("kaito:prefix_cache_hits_total", 0.0) - hits0)
    check(out["prefix_cache_hits"] >= 1,
          "repeating prompt (a) hit the prefix cache 0 times")

    check(m.get("kaito:requests_failed_total") == 0,
          f"requests_failed_total = {m.get('kaito:requests_failed_total')}")
    check(m.get("kaito:engine_fatal_total") == 0,
          f"engine_fatal_total = {m.get('kaito:engine_fatal_total')}")
    check(m.get("kaito:generation_tokens_total") == asked,
          f"generation_tokens_total = "
          f"{m.get('kaito:generation_tokens_total')}, asked for {asked}")
    out["completion_tokens"] = asked
    return out


# ---------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------

def check_health(health: dict, expect_platform: str) -> None:
    want = EXPECT[expect_platform]
    check(health.get("attention") == want["attention"],
          f"attention implementation {health.get('attention')!r}, "
          f"expected {want['attention']!r}")
    source = (health.get("hbm_sizing") or {}).get("source")
    check(source == want["hbm_source"],
          f"hbm_sizing.source {source!r}, expected {want['hbm_source']!r}")
    check(health.get("prefix_cache") == "native",
          f"prefix cache {health.get('prefix_cache')!r}, expected 'native'")


def check_memory(devices: list, balanced: bool) -> None:
    """No device over its limit; on a leg that spreads over every device,
    their bytes in use within 10% of each other."""
    used = []
    for d in devices:
        if d.get("bytes_limit") is None:     # the CPU reports none
            continue
        check(d["peak_bytes_in_use"] <= d["bytes_limit"],
              f"device {d['id']} peaked at {d['peak_bytes_in_use']} of "
              f"{d['bytes_limit']} bytes")
        used.append(d["bytes_in_use"])
    if balanced and len(used) > 1:
        check(max(used) - min(used) <= 0.10 * max(used),
              f"bytes_in_use differ by more than 10% across devices: "
              f"{used}")


def serve_leg(leg: str, args, extra_args: list, replicas: int = 1,
              balanced: bool = False) -> tuple:
    """(what the leg measured, the server's /health when it came up)"""
    with Server(leg, args.model, extra_args) as srv:
        health = srv.wait_healthy(args.expect_platform)
        out = {"seconds_to_healthy":
               round(time.monotonic() - srv.t_launch, 1)}
        log(f"{leg}: healthy after {out['seconds_to_healthy']}s on "
            f"{health.get('device_count')} x {health.get('device_kind')}")
        check_health(health, args.expect_platform)
        out.update(run_requests(srv, replicas))
        _, body = srv.request("/health", timeout=30.0)
        after = json.loads(body)
        check_memory(after["devices"], balanced)
        out["devices"] = after["devices"]
        out["hbm_sizing"] = after.get("hbm_sizing")
        srv.stop()
    return out, health


def kernels_leg() -> tuple:
    """(error and tolerance per kernel, the device the child saw)"""
    log_path = os.path.join(OUT_DIR, "kernels.log")
    with open(log_path, "w") as err:
        proc = subprocess.Popen(
            [sys.executable, os.path.join("benchmarks", "kernel_bench.py"),
             "--parity"], cwd=ROOT, env=child_env(),
            stdout=subprocess.PIPE, stderr=err, text=True,
            start_new_session=True)
        try:
            stdout, _ = proc.communicate(timeout=900)
        finally:
            stop_child(proc, grace_s=5.0)
    rows, device = {}, None
    for line in stdout.splitlines():
        if line.startswith("{"):
            row = json.loads(line)
            if "name" in row:
                rows[row["name"]] = row
            else:
                device = row
    bad = [f"{n}: " + (r.get("error")
                       or f"max_err {r['max_err']} > tol {r['tol']}")
           for n, r in rows.items() if not r["ok"]]
    missing = [n for n in REQUIRED_KERNELS if n not in rows]
    check(not bad and not missing and device and proc.returncode == 0,
          f"kernels leg failed (exit {proc.returncode}); missing "
          f"{missing}; failed:\n" + "\n".join(bad) + "\n" + tail(log_path))
    return ({n: {"max_err": r["max_err"], "tol": r["tol"]}
             for n, r in rows.items()}, device)


def probe_device_count() -> int:
    """Only when tp/dp were asked for without an earlier leg: normally
    the count comes from the first server's /health."""
    res = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.device_count())"],
        cwd=ROOT, env=child_env(), capture_output=True, text=True,
        timeout=300)
    check(res.returncode == 0, f"device probe failed: {res.stderr[-400:]}")
    return int(res.stdout.split()[-1])


def versions() -> dict:
    out = {"python": sys.version.split()[0]}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="phi-4-mini-instruct")
    ap.add_argument("--expect-platform", default="tpu",
                    choices=sorted(EXPECT))
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="comma-separated subset of " + ",".join(LEGS)
                         + " (a four-chip leg costs four chips' time)")
    args = ap.parse_args()
    picked = args.legs.split(",")
    unknown = sorted(set(picked) - set(LEGS))
    if unknown:
        ap.error(f"unknown legs {unknown}")

    if not os.path.isdir(os.path.join(ROOT, "kaito_tpu")):
        log(f"no kaito_tpu package beside {__file__}: nothing to smoke")
        return 1
    os.makedirs(OUT_DIR, exist_ok=True)
    log(f"model {args.model}, expecting platform {args.expect_platform}")
    result = {"ok": False, "model": args.model,
              "expect_platform": args.expect_platform,
              "versions": versions(),
              "compile_cache": {"dir": cache_dir(),
                                "entries_before": cache_entries()},
              "legs": {leg: "skipped: not selected" for leg in LEGS}}
    legs = result["legs"]
    health = None
    leg = "build"
    try:
        build_native()
        for leg in LEGS:
            if leg not in picked:
                continue
            t0 = time.monotonic()
            if leg == "kernels":
                if args.expect_platform != "tpu":
                    legs[leg] = ("skipped: --expect-platform "
                                 f"{args.expect_platform}; the Pallas "
                                 "kernels compile for a TPU only")
                    continue
                detail, seen = kernels_leg()
            elif leg == "serve":
                detail, seen = serve_leg(leg, args, [])
            else:
                n = (health["device_count"] if health
                     else probe_device_count())
                if n < 2:
                    legs[leg] = f"skipped: {n} device"
                    continue
                flag = ("--tensor-parallel-size" if leg == "tp"
                        else "--data-parallel-size")
                detail, seen = serve_leg(leg, args, [flag, str(n)],
                                         replicas=n if leg == "dp" else 1,
                                         balanced=True)
            health = health or seen    # the first leg names the device
            legs[leg] = "ok"
            result[leg] = detail
            log(f"{leg}: ok in {time.monotonic() - t0:.0f}s")
        check(health is not None, "no leg ran")
        result["ok"] = True
    except (SmokeError, OSError, subprocess.SubprocessError,
            KeyError, ValueError) as e:
        legs[leg] = "failed"
        result["failed"] = f"{leg}: {type(e).__name__}: {e}"
        log(result["failed"])
    if health:
        result["device"] = {"platform": health["platform"],
                            "kind": health["device_kind"],
                            "count": health["device_count"]}
        # launch-to-ready of the first server that ran
        first = next((result[leg] for leg in ("serve", "tp", "dp")
                      if leg in result), {})
        for key in ("seconds_to_healthy", "seconds_to_first_token"):
            result[key] = first.get(key)
    result["compile_cache"]["entries_after"] = cache_entries()
    line = json.dumps(result)
    with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
        f.write(line + "\n")
    if not result["ok"]:
        print(line, file=sys.stderr, flush=True)
        return 1
    print(line)
    # the verdict, last and alone: exactly these keys
    print(json.dumps({"ok": True, "device": result["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
