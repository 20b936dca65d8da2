"""Decode-throughput benchmark. Prints ONE JSON line to stdout.

Measures steady-state continuous-batching decode tokens/s/chip on the
local accelerator with synthetic weights (bench is weight-value
independent).  Model: phi-4-mini-instruct (the reference's own latency
benchmark model, website/docs/gpu-benchmarks.md) in bf16 on TPU; a tiny
llama on CPU so the script stays runnable anywhere.

vs_baseline anchors to the repo north star of 2,000 tokens/s/chip
(BASELINE.md "Targets for this repo").

Structure:

- The ORCHESTRATOR (default mode) never imports jax: a chip belongs to
  one process at a time, so it runs each measurement phase as a child
  subprocess — one at a time, each in its own process group with a hard
  timeout — and merges each phase's JSON into the result.
- It measures a chip.  A phase that finds no accelerator fails, a
  kernel that does not compile is an error (there is no Pallas->JAX
  fallback), and any phase error makes the run exit non-zero.
  ``--force-cpu`` is the by-name rehearsal of the phase plumbing at a
  tiny size; its numbers say nothing about a device.
- Phases (``--phase``): ``raw`` (ladder decode throughput +
  TTFT; run twice for the bf16-vs-int8-KV row), ``serve``
  (engine-under-load; run twice for the speculation on/off row),
  ``prefix`` (cold-vs-warm prefix-hit TTFT), ``int8_8b`` (8B-class
  int8 serving), ``pd`` (prefill/decode KV hand-off latency), ``cp``
  (context-parallel prefill at 8k, plus a 32k attention-critical-path
  leg).  Every throughput row carries ``mfu_pct``/``hbm_roofline_pct``.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np

BASELINE_TOK_S = 2000.0


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# orchestrator helpers (no jax imports allowed above the phase functions)
# ---------------------------------------------------------------------------

def run_phase(name: str, extra, timeout_s: float):
    """Run one phase as a child in its own process group; return its
    parsed JSON result or an {"error": ...} dict.  A hang kills the
    child's whole group, never this orchestrator."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name] + extra
    log(f"[bench] phase {name}: timeout {timeout_s:.0f}s")
    t0 = time.monotonic()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=sys.stderr,
                            start_new_session=True, text=True)
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        log(f"[bench] phase {name} exceeded {timeout_s:.0f}s; killing group")
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except Exception:
            proc.kill()
        proc.wait()
        return {"error": f"phase {name} timed out after {timeout_s:.0f}s"}
    dt = time.monotonic() - t0
    last = ""
    for line in (out or "").strip().splitlines():
        if line.startswith("{"):
            last = line
    if proc.returncode != 0 and not last:
        return {"error": f"phase {name} exited rc={proc.returncode}"}
    try:
        res = json.loads(last)
    except Exception:
        return {"error": f"phase {name} produced no JSON (rc={proc.returncode})"}
    log(f"[bench] phase {name} done in {dt:.0f}s: {res}")
    return res


def orchestrate(args):
    t_start = time.monotonic()
    deadline = args.deadline
    merged = {"metric": "decode_throughput", "value": 0.0,
              "unit": "tokens/s/chip", "vs_baseline": 0.0}

    def remaining():
        return deadline - 60.0 - (time.monotonic() - t_start)

    passthru = ["--force-cpu"] if args.force_cpu else []
    if args.model:
        passthru += ["--model", args.model]
    if args.batch:
        passthru += ["--batch", str(args.batch)]
    if args.attn_impl:
        passthru += ["--attn-impl", args.attn_impl]
    if args.quant:
        passthru += ["--quant", args.quant]
    if args.kv_dtype:
        passthru += ["--kv-dtype", args.kv_dtype]
    passthru += ["--prompt-len", str(args.prompt_len),
                 "--decode-steps", str(args.decode_steps),
                 "--repeats", str(args.repeats)]

    # --- phase: raw ladder (headline number).  It is also the run's
    # device check: with no platform back nothing was measured, and the
    # run ends without a result ---
    res = run_phase("raw", passthru, min(remaining(), 700.0))
    if "platform" not in res:
        log(f"[bench] raw phase failed: {res.get('error', res)}")
        sys.exit(1)
    merged.update(res)
    on_tpu = res["platform"] != "cpu"
    model_name = args.model or ("phi-4-mini-instruct" if on_tpu
                                else "tiny-llama-test")

    # --- phase: raw ladder again with an int8 KV pool (the bf16-vs-int8
    # decode row; same batch/shape knobs, only the page pool changes) ---
    if (not args.skip_kv_int8 and args.kv_dtype != "int8"
            and remaining() > 60):
        res = run_phase("raw", passthru + ["--kv-dtype", "int8"],
                        min(remaining(), 700.0))
        if "value" in res and res.get("value", 0) > 0:
            merged["kv_int8_decode_tok_s"] = res["value"]
            merged["kv_int8_metric"] = res.get("metric", "")
            for k in ("mfu_pct", "hbm_roofline_pct", "batch", "ttft_p50_ms"):
                if k in res:
                    merged[f"kv_int8_{k}"] = res[k]
            if merged.get("value", 0) > 0:
                merged["kv_int8_speedup"] = round(
                    res["value"] / merged["value"], 3)
        else:
            merged.setdefault("errors", []).append(
                res.get("error", "kv-int8 raw failed"))

    # --- phase: bf16-vs-int8-vs-int4 WEIGHT ladder (same batch/shape
    # knobs, only the weight bytes change; docs/quantization.md).
    # Decode is param-bandwidth-bound, so each halving of the weight
    # stream should move tok/s — weight_quant_speedup_* is that claim
    # measured against the bf16 headline above.  Quality rides in the
    # separate wquant_quality phase (golden-prompt divergence). ---
    if not args.skip_wquant and not args.quant:
        for scheme in ("int8", "int4"):
            if remaining() <= 60:
                break
            res = run_phase("raw", passthru + ["--quant", scheme],
                            min(remaining(), 700.0))
            if "value" in res and res.get("value", 0) > 0:
                merged[f"weight_{scheme}_decode_tok_s"] = res["value"]
                merged[f"weight_{scheme}_metric"] = res.get("metric", "")
                for k in ("mfu_pct", "hbm_roofline_pct", "batch",
                          "ttft_p50_ms"):
                    if k in res:
                        merged[f"weight_{scheme}_{k}"] = res[k]
                if merged.get("value", 0) > 0:
                    merged[f"weight_quant_speedup_{scheme}"] = round(
                        res["value"] / merged["value"], 3)
            else:
                merged.setdefault("errors", []).append(
                    res.get("error", f"weight-{scheme} raw failed"))

    # --- phase: weight-quant quality legs (CPU-cheap: greedy goldens
    # on a real checkpoint per scheme, count divergent prompts) ---
    if not args.skip_wquant and remaining() > 90:
        res = run_phase("wquant_quality", [], min(remaining(), 500.0))
        if "error" not in res:
            merged.update(res)
        else:
            merged.setdefault("errors", []).append(res["error"])

    # --- phase: serving path (engine under load) ---
    if not args.skip_server_bench and remaining() > 120:
        res = run_phase("serve", passthru, min(remaining(), 650.0))
        if "server_tok_s" in res:
            merged.update(res)
        else:
            merged.setdefault("errors", []).append(res.get("error", "serve failed"))

    # --- phase: serving with n-gram speculation ON (spec on/off row;
    # speculation engages in the low-batch latency regime, so this row
    # reports its own batch and acceptance rate, not a speedup claim
    # against the saturated number above) ---
    if not args.skip_server_bench and not args.skip_spec_bench \
            and remaining() > 120:
        res = run_phase("serve", passthru + ["--spec-ngram", "4"],
                        min(remaining(), 650.0))
        if "server_tok_s" in res:
            merged["spec_server_tok_s"] = res["server_tok_s"]
            for k in ("server_batch", "spec_accept_rate", "mfu_pct",
                      "hbm_roofline_pct"):
                if k in res:
                    merged[f"spec_{k}"] = res[k]
        else:
            merged.setdefault("errors", []).append(
                res.get("error", "spec serve failed"))

    # --- phase: serving with DRAFT-MODEL speculation ON — greedy and
    # sampled legs (self-draft: acceptance is an upper bound, but the
    # whole propose/verify/accept machinery including rejection
    # sampling is the code under test; docs/speculative.md).  Paired
    # with the spec-off serve row + the sampled baseline below, this
    # fills the draft on/off x greedy/sampled matrix ---
    if not args.skip_server_bench and not args.skip_spec_bench \
            and remaining() > 120:
        res = run_phase("serve", passthru + ["--spec-draft", "self"],
                        min(remaining(), 650.0))
        if "server_tok_s" in res:
            merged["spec_draft_server_tok_s"] = res["server_tok_s"]
            for k in ("server_batch", "spec_accept_rate",
                      "spec_mean_depth", "mfu_pct", "hbm_roofline_pct"):
                if k in res:
                    merged[f"spec_draft_{k}"] = res[k]
        else:
            merged.setdefault("errors", []).append(
                res.get("error", "spec-draft serve failed"))
    if not args.skip_server_bench and not args.skip_spec_bench \
            and remaining() > 120:
        res = run_phase("serve",
                        passthru + ["--spec-temp", "0.8"],
                        min(remaining(), 650.0))
        if "server_tok_s" in res:
            merged["sampled_server_tok_s"] = res["server_tok_s"]
        else:
            merged.setdefault("errors", []).append(
                res.get("error", "sampled serve failed"))
    if not args.skip_server_bench and not args.skip_spec_bench \
            and remaining() > 120:
        res = run_phase("serve",
                        passthru + ["--spec-draft", "self",
                                    "--spec-temp", "0.8"],
                        min(remaining(), 650.0))
        if "server_tok_s" in res:
            merged["spec_draft_sampled_server_tok_s"] = res["server_tok_s"]
            for k in ("spec_accept_rate", "spec_mean_depth"):
                if k in res:
                    merged[f"spec_draft_sampled_{k}"] = res[k]
        else:
            merged.setdefault("errors", []).append(
                res.get("error", "spec-draft sampled serve failed"))

    # --- phase: prefix-hit TTFT (cold vs warm shared-prefix prompt;
    # the row EPP affinity routing banks on, docs/routing.md) ---
    if not args.skip_prefix_bench and remaining() > 90:
        res = run_phase("prefix", passthru, min(remaining(), 400.0))
        if "error" not in res:
            merged.update(res)
        else:
            merged.setdefault("errors", []).append(res["error"])

    # --- phase: packed-prefill burst (tokens/dispatch + TTFT, pack
    # on-vs-off; docs/prefill.md) ---
    if not args.skip_prefill_bench and remaining() > 90:
        res = run_phase("prefill_burst", passthru, min(remaining(), 400.0))
        if "error" not in res:
            merged.update(res)
        else:
            merged.setdefault("errors", []).append(res["error"])

    # --- phase: int8 8B-class serving (TPU only) ---
    if on_tpu and not args.skip_int8_8b and not args.quant \
            and remaining() > 150:
        res = run_phase("int8_8b", [], min(remaining(), 650.0))
        if "server_tok_s" in res:
            merged["int8_8b_model"] = "llama-3.1-8b-instruct"
            merged["int8_8b_server_tok_s"] = res["server_tok_s"]
            for k, v in res.items():
                if k.startswith("ttft"):
                    merged["int8_8b_" + k] = v
        else:
            merged.setdefault("errors", []).append(
                res.get("error", "int8_8b failed"))

    # --- phase: P/D KV hand-off latency ---
    if not args.skip_pd_bench and remaining() > 90:
        res = run_phase("pd", passthru, min(remaining(), 400.0))
        if "error" not in res:
            merged.update(res)
        else:
            merged.setdefault("errors", []).append(res["error"])

    # --- phase: cluster KV pool cross-replica fetch (docs/kv-pool.md) ---
    if not args.skip_pd_bench and remaining() > 90:
        res = run_phase("kvpool", passthru, min(remaining(), 300.0))
        if "error" not in res:
            merged.update(res)
        else:
            merged.setdefault("errors", []).append(res["error"])

    # --- phase: multi-turn conversation replay over the KV tiers
    # (docs/kv-pool.md "Tier 3: SSD") — schema-stable: the keys exist
    # at 0.0 even when the leg is skipped or fails, so result diffing
    # across runs never keys on a missing column ---
    conv_keys = ("conversation_turn1_ttft_s", "conversation_turn2_ttft_s",
                 "conversation_turn3_ttft_s", "conversation_turn3_vs_turn1",
                 "conversation_host_hits", "conversation_disk_hits",
                 "conversation_import_tokens",
                 "conversation_disk_read_bytes_s")
    if not args.skip_conversation_bench and remaining() > 90:
        res = run_phase("conversation", passthru, min(remaining(), 400.0))
        if "error" not in res:
            merged.update(res)
        else:
            merged.setdefault("errors", []).append(res["error"])
    for k in conv_keys:
        merged.setdefault(k, 0.0)

    # --- phase: multi-LoRA hot-load + adapter decode (docs/multi-lora.md) ---
    if not args.skip_lora_bench and remaining() > 90:
        extra = ["--force-cpu"] if args.force_cpu else []
        res = run_phase("lora", extra, min(remaining(), 300.0))
        if "error" not in res:
            merged.update(res)
        else:
            merged.setdefault("errors", []).append(res["error"])

    # --- phase: grammar-constrained decoding (docs/structured-output.md) ---
    if not args.skip_structured_bench and remaining() > 90:
        extra = ["--force-cpu"] if args.force_cpu else []
        res = run_phase("structured", extra, min(remaining(), 300.0))
        if "error" not in res:
            merged.update(res)
        else:
            merged.setdefault("errors", []).append(res["error"])

    # --- phase: context-parallel prefill scaling (virtual 8-dev mesh) ---
    if not args.skip_cp_bench and remaining() > 120:
        res = run_phase("cp", ["--cp-tokens", str(args.cp_tokens)],
                        min(remaining(), 600.0))
        if "error" not in res:
            merged.update(res)
        else:
            merged.setdefault("errors", []).append(res["error"])

    # --- phase: multi-chip decode ladder (virtual 8-dev mesh): tp/pp
    # rows + the comm-overlap A-B leg (docs/multichip.md) ---
    if not args.skip_multichip_bench and remaining() > 90:
        res = run_phase("multichip", [], min(remaining(), 500.0))
        if "error" not in res:
            merged.update(res)
        else:
            merged.setdefault("errors", []).append(res["error"])

    # --- phase: 32k CP leg, attention-critical-path only (a full 32k
    # chunked-prefill engine run takes tens of minutes on this host;
    # the per-chip shard-attention time is the quantity that actually
    # bounds TTFT and it measures in seconds) ---
    if not args.skip_cp_bench and remaining() > 90:
        res = run_phase("cp", ["--cp-tokens", "32768", "--cp-attn-only"],
                        min(remaining(), 400.0))
        if "error" not in res:
            merged.update({f"cp32k_{k}": v for k, v in res.items()})
        else:
            merged.setdefault("errors", []).append(res["error"])

    if merged.get("value", 0) <= 0 and merged.get("server_tok_s"):
        # raw phase lost but serving survived: promote the serving
        # number so the headline reflects a real measurement
        merged["metric"] = f"{model_name}_serving_throughput"
        merged["value"] = merged["server_tok_s"]
        merged["vs_baseline"] = round(merged["server_tok_s"] / BASELINE_TOK_S, 3)
    print(json.dumps(merged), flush=True)
    if merged.get("errors"):
        sys.exit(1)


# ---------------------------------------------------------------------------
# phases (child processes; these DO import jax)
# ---------------------------------------------------------------------------

def _init_jax(force_cpu: bool = False):
    """First call of every phase child: place the compile cache, then
    demand an accelerator unless the CPU was asked for by name."""
    if force_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from kaito_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    import jax

    if not force_cpu and jax.default_backend() == "cpu":
        log("bench.py measures an accelerator and jax found none "
            "(--force-cpu rehearses the phases at a tiny size)")
        sys.exit(1)
    return jax


def bench_serving_path(model_name: str, on_tpu: bool, quant: str = "",
                       spec_ngram: int = 0, spec_draft: str = "",
                       spec_temp: float = 0.0):
    """Serving-path benchmark: the REAL engine (scheduler, paged KV,
    chunked prefill interleave, continuous admission) under sustained
    load — the regime the reference's vLLM benchmark sweeps
    (benchmark_entrypoint.py:48-50), not the idle-queue decode loop.

    Phase 1 (saturation): closed-loop clients keep every slot busy and
    the queue never empty; throughput = Δgeneration_tokens/Δt from the
    engine counters over a timed window.
    Phase 2 (TTFT under load): load throttles to half the slots so
    admission isn't queue-bound, then 2048-token-prompt probes measure
    p50 time-to-first-token (BASELINE.md's TTFT contract shape).

    Returns {"server_tok_s", "server_tpm", "ttft_p50_ms@2048in", ...}.
    """
    if on_tpu:
        # walked down on HBM exhaustion: the fused-decode program's
        # sampler temps ([B, 200k] sorts) live in the overhead budget
        # and can tip a 16 GiB chip at the widest batch
        seq_ladder = (96, 64, 48)
    else:
        seq_ladder = (4,)
    if spec_ngram or spec_draft:
        # speculation only engages at/below speculative_max_batch: the
        # spec on/off row measures the low-batch latency regime
        seq_ladder = (8,) if on_tpu else (4,)
    last_msg = ""
    for i, max_seqs in enumerate(seq_ladder):
        try:
            return _bench_serving_once(model_name, on_tpu, quant, max_seqs,
                                       spec_ngram=spec_ngram,
                                       spec_draft=spec_draft,
                                       spec_temp=spec_temp)
        except Exception as e:
            msg = f"{type(e).__name__}: {str(e)[:300]}"
            retryable = ("RESOURCE_EXHAUSTED" in str(e)
                         or isinstance(e, _ServingStall))
            # drop the traceback BEFORE the next rung: it pins the
            # failed attempt's engine (weights + KV pool) in HBM, which
            # would OOM every lower rung too
            e.__traceback__ = None
            del e
            if not retryable or i + 1 == len(seq_ladder):
                raise RuntimeError(f"serving bench failed at batch "
                                   f"{max_seqs}: {msg}")
            last_msg = msg
            log(f"[server] batch {max_seqs} failed ({msg}); walking down")
    raise RuntimeError(last_msg)


class _ServingStall(RuntimeError):
    """The engine loop swallowed step failures into a silent stall
    (fails in-flight requests and carries on) — retryable at a
    narrower batch."""


def _bench_serving_once(model_name: str, on_tpu: bool, quant: str,
                        max_seqs: int, spec_ngram: int = 0,
                        spec_draft: str = "",
                        spec_temp: float = 0.0) -> dict:
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine, SamplingParams

    if on_tpu:
        prompt_len, out_toks = 128, 256
        window_s, warm_min_s, warm_max_s = 45.0, 15.0, 300.0
        probe_len, n_probes = 2048, 8
        max_len, dtype = 2560, "bfloat16"
        buckets = (128, 512)      # 512 = chunked-prefill ctx bucket
    else:   # tiny, CPU-testable shape of the same phases
        prompt_len, out_toks = 32, 16
        window_s, warm_min_s, warm_max_s = 5.0, 1.0, 120.0
        probe_len, n_probes = 256, 3
        max_len, dtype = 320, "float32"
        buckets = (32, 256)

    # prefix caching OFF: the synthetic prompts are random, and the
    # honest sustained number must not ride accidental prefix hits
    cfg = EngineConfig(model=model_name, dtype=dtype, kv_dtype=dtype,
                       max_num_seqs=max_seqs, max_model_len=max_len,
                       prefill_buckets=buckets, enable_prefix_caching=False,
                       quantization=quant, disable_rate_limit=True,
                       speculative_ngram=spec_ngram,
                       speculative_draft=spec_draft,
                       itl_enabled=True,
                       max_queue_len=100000)
    eng = InferenceEngine(cfg)
    eng.start()
    vocab = eng.md.arch.vocab_size

    stop = threading.Event()
    throttled = threading.Event()   # phase 2: most clients exit
    n_clients = max_seqs + max(4, max_seqs // 2)
    keep_n = max(2, max_seqs // 2)  # clients surviving the throttle

    def client(idx):
        crng = np.random.RandomState(1000 + idx)
        while not stop.is_set():
            if throttled.is_set() and idx >= keep_n:
                return
            req = eng.submit(
                crng.randint(1, min(vocab, 255), (prompt_len,)).tolist(),
                SamplingParams(max_tokens=out_toks,
                               temperature=spec_temp,
                               ignore_eos=True))
            for _ in req.stream():
                pass

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(n_clients)]
    for t in threads:
        t.start()

    ttfts = []
    try:
        # warmup: wait out the compiles until the engine is emitting at
        # a steady clip (decode counter advancing with all slots busy)
        t0 = time.monotonic()
        last = -1
        warmed = False
        while time.monotonic() - t0 < warm_max_s:
            time.sleep(1.0)
            d = eng.counters["decode_steps_total"]
            if (time.monotonic() - t0 >= warm_min_s and d > 50
                    and eng.num_running >= max(1, max_seqs // 2)
                    and d != last):
                warmed = True
                break
            last = d
        if not warmed:
            # a compile OOM or repeated step failure shows up as a
            # stalled (or never-started) decode counter; surface it so
            # the batch ladder can walk down instead of measuring ~0
            raise _ServingStall(
                f"engine never reached steady decode within "
                f"{warm_max_s:.0f}s at batch {max_seqs} "
                f"(steps={eng.counters['decode_steps_total']}, "
                f"running={eng.num_running})")
        log(f"[server] warm after {time.monotonic() - t0:.0f}s; "
            f"running={eng.num_running} waiting={eng.num_waiting}")

        g0 = eng.counters["generation_tokens_total"]
        s0 = eng.counters["decode_steps_total"]
        t0 = time.monotonic()
        time.sleep(window_s)
        dt = time.monotonic() - t0
        gen = eng.counters["generation_tokens_total"] - g0
        steps = eng.counters["decode_steps_total"] - s0
        tok_s = gen / dt
        log(f"[server] sustained: {gen} tokens in {dt:.1f}s -> "
            f"{tok_s:.0f} tok/s ({steps} decode steps, "
            f"waiting={eng.num_waiting}, preempt="
            f"{eng.counters['preemptions_total']})")

        # phase 2: throttle to half the slots, then TTFT probes
        throttled.set()
        t0 = time.monotonic()
        while (eng.num_waiting > 0 or eng.num_running > keep_n + 2) \
                and time.monotonic() - t0 < 90:
            time.sleep(0.5)
        log(f"[server] throttled to ~{keep_n} live clients in "
            f"{time.monotonic() - t0:.0f}s (running={eng.num_running}, "
            f"waiting={eng.num_waiting})")
        prng = np.random.RandomState(7)
        for i in range(n_probes):
            req = eng.submit(
                prng.randint(1, min(vocab, 255), (probe_len,)).tolist(),
                SamplingParams(max_tokens=8, temperature=0.0,
                               ignore_eos=True))
            sub = time.monotonic()
            first = next(iter(req.stream()), None)
            if first is not None:
                ttfts.append((time.monotonic() - sub) * 1e3)
                for _ in req.stream():
                    pass
    finally:
        # deterministic phase boundary: stop() fails in-flight requests
        # so every client thread unblocks and the engine (weights + KV
        # pool) is actually collectable before the next phase sizes
        # itself from free HBM
        stop.set()
        eng.stop()
        for t in threads:
            t.join(timeout=10)
    out = {
        "server_tok_s": round(tok_s, 1),
        "server_tpm": round(tok_s * 60.0),
        "server_batch": max_seqs,
        "server_out_toks": out_toks,
    }
    out.update(_devprof_pcts(eng))
    out.update(_itl_metrics(eng))
    # every throughput row carries its roofline position (VERDICT r5
    # weak #1): how close this number is to the chip's compute and
    # HBM-bandwidth peaks
    out.update(_roofline_metrics(
        eng.md.arch, tok_s, max_seqs, prompt_len + out_toks, quant=quant))
    if spec_ngram:
        proposed = eng.counters.get("spec_proposed_tokens_total", 0)
        accepted = eng.counters.get("spec_accepted_tokens_total", 0)
        out["spec_ngram"] = spec_ngram
        if proposed:
            out["spec_accept_rate"] = round(accepted / proposed, 3)
    if spec_draft:
        proposed = eng.counters.get("spec_draft_proposed_tokens_total", 0)
        accepted = eng.counters.get("spec_draft_accepted_tokens_total", 0)
        rows = eng.counters.get("spec_draft_rows_total", 0)
        out["spec_draft"] = spec_draft
        if spec_temp:
            out["spec_temp"] = spec_temp
        if proposed:
            out["spec_accept_rate"] = round(accepted / proposed, 3)
        if rows:
            # mean REALIZED depth per drafting slot-round (after
            # remaining-budget clipping and the controller's AIMD
            # moves) — the lever the adaptive depth actually pulled,
            # not the configured ceiling
            out["spec_mean_depth"] = round(proposed / rows, 2)
    if ttfts:
        p50 = sorted(ttfts)[len(ttfts) // 2]
        log(f"[server] TTFT@{probe_len}in under half-load: "
            f"p50 {p50:.0f} ms (n={len(ttfts)})")
        out[f"ttft_p50_ms@{probe_len}in"] = round(p50, 1)
    return out


def _roofline_metrics(arch, tok_s, batch, ctx, *, quant="", kv_dtype="",
                      page_size=64):
    """MFU and HBM-roofline utilization for a decode-throughput number
    vs the peaks of the chip jax reports (sku/catalog.py; an unknown
    device kind is an error).  A --force-cpu rehearsal has no roofline
    and gets no such columns.

    Decode does ~2 FLOPs per parameter per token and re-reads the full
    weight set plus every live sequence's KV each step, so:

      mfu_pct          = 100 * tok_s * 2 * params / peak_flops
      hbm_roofline_pct = 100 * tok_s * bytes_per_token / peak_bw
      bytes_per_token  = (param_bytes + batch * ctx * kv_bpt) / batch

    An int8 KV pool halves kv_bpt (plus the fp32 page-scale rows), so
    the same tok/s scores LOWER here — headroom the quantized cache
    opened up."""
    import jax

    from kaito_tpu.sku.catalog import chip_for_device_kind

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return {}
    chip = chip_for_device_kind(dev.device_kind)
    n_params = arch.param_count()
    # int4 dequantizes to bf16/fp32 in-register before the MXU dot, so
    # its compute peak is the bf16 one; only int8 (native int8 dots)
    # earns the int8_tops peak
    peak_flops = (chip.int8_tops if quant == "int8"
                  else chip.bf16_tflops) * 1e12
    # bytes/param streamed each decode step: bf16 2, int8 1 (+fp32
    # per-out-channel scale, negligible), int4 0.5 + fp32 per-group
    # scales at g=128 -> 0.5 + 4/128 = 0.53125
    param_bytes = n_params * {"": 2.0, "int8": 1.0,
                              "int4": 0.53125}.get(quant, 2.0)
    kv_elt = 1 if kv_dtype == "int8" else 2
    kv_bpt = (2.0 * arch.num_layers * arch.num_kv_heads
              * arch.head_dim * kv_elt)
    if kv_dtype == "int8":
        kv_bpt += 8.0 * arch.num_layers * arch.num_kv_heads / page_size
    bytes_per_tok = (param_bytes + batch * ctx * kv_bpt) / max(1, batch)
    return {
        "mfu_pct": round(100.0 * tok_s * 2.0 * n_params / peak_flops, 2),
        "hbm_roofline_pct": round(
            100.0 * tok_s * bytes_per_tok / (chip.hbm_gbps * 1e9), 2),
    }


def _devprof_pcts(eng=None) -> dict:
    """Device-time attribution columns from the engine's sampling
    device profiler (docs/observability.md).  Schema-stable: both read
    0.0 when devprof is off (the default for bench engines — sampling
    perturbs the number being measured) so BENCH_*.json stays diffable
    across rounds, same convention as device_idle_pct/dispatch_gap_ms."""
    prof = getattr(eng, "devprof", None) if eng is not None else None
    last = (prof.last() if prof is not None else None) or {}
    return {
        "comm_pct": round(float(last.get("comm_pct", 0.0)), 2),
        "overlap_pct": round(
            float(last.get("comm_compute_overlap_pct", 0.0)), 2),
    }


def _itl_metrics(eng=None) -> dict:
    """True per-token ITL columns from the engine's retire-path stamps
    (kaito:inter_token_latency_seconds).  Schema-stable: all three read
    0.0 when the feature is off or no gaps were observed (the raw
    ladder has no engine at all), same convention as
    device_idle_pct/dispatch_gap_ms."""
    h = getattr(eng, "itl_hist", None) if eng is not None else None
    if h is None:
        return {"itl_p50_ms": 0.0, "itl_p99_ms": 0.0, "itl_stall_count": 0}
    return {
        "itl_p50_ms": round(h.percentile(0.5) * 1e3, 3),
        "itl_p99_ms": round(h.percentile(0.99) * 1e3, 3),
        "itl_stall_count": int(eng.counters.get("itl_stalls_total", 0)),
    }


def phase_raw(args):
    """Raw ladder: prefill + fused decode loop at the widest batch that
    fits, plus steady-state batch-1 TTFT."""
    jax = _init_jax(force_cpu=args.force_cpu)
    import jax.numpy as jnp

    from kaito_tpu.engine.kv_cache import create_kv_cache
    from kaito_tpu.engine.model import TransformerLM
    from kaito_tpu.models import get_model_by_name

    platform = jax.devices()[0].platform
    on_tpu = platform not in ("cpu",)
    model_name = args.model or ("phi-4-mini-instruct" if on_tpu
                                else "tiny-llama-test")
    # decode is param-bandwidth-bound, so tokens/s/chip scales with
    # batch until KV + params exhaust the 16 GiB v5e HBM (measured:
    # 64 -> 3.8k, 96 -> 5.0k, 112 -> 5.5k tok/s; 128 OOMs).  The
    # ladder walks down on RESOURCE_EXHAUSTED so a fragmentation
    # hiccup degrades the number instead of zeroing it.
    if args.batch:
        batch_ladder = [args.batch]
    elif not on_tpu:
        batch_ladder = [4]
    elif args.quant:
        # int8 halves (int4 ~quarters) weight bytes -> deeper batches
        # fit (int8 measured: 112 -> 6.7k, 160 -> 7.3k, 224 -> 7.8k
        # tok/s); int4 reuses the same ladder — KV, not weights, caps
        # batch there
        batch_ladder = [224, 160, 112, 64]
    else:
        batch_ladder = [112, 96, 64]
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    # KV pool dtype rides independently of compute dtype: int8 pages +
    # fp32 page scales (engine/kv_cache.py) halve the per-step KV read
    kv_dt = jnp.int8 if args.kv_dtype == "int8" else dtype
    md = get_model_by_name(model_name)
    arch = md.arch

    # default: pallas kernels on TPU (engine auto), pure JAX on CPU
    attn_impl = args.attn_impl or ("pallas" if on_tpu else "jax")
    model = TransformerLM(arch, dtype=dtype, attn_impl=attn_impl)
    log(f"attention impl: {attn_impl}")
    t0 = time.monotonic()
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    log(f"params ready in {time.monotonic() - t0:.1f}s "
        f"({sum(x.nbytes for x in jax.tree.leaves(params)) / 2**30:.2f} GiB)")
    if args.quant:
        from functools import partial

        from kaito_tpu.engine.quant import quantize_params

        params = jax.jit(partial(quantize_params,
                                 scheme=args.quant))(params)
        jax.block_until_ready(params)
        log(f"{args.quant} weights: "
            f"{sum(x.nbytes for x in jax.tree.leaves(params)) / 2**30:.2f} GiB")

    page_size = 64
    total_len = args.prompt_len + args.decode_steps
    pages_per_seq = -(-total_len // page_size)
    steps = args.decode_steps

    def run_path(impl: str, model, batch: int):
        """Prefill + timed decode for one attention impl. A fresh model
        per impl keeps JAX's bound-method jit cache from serving a
        stale trace of the other path."""
        num_pages = batch * pages_per_seq + 1
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(
            rng.randint(0, arch.vocab_size, (batch, args.prompt_len)),
            jnp.int32)
        true_lens = jnp.full((batch,), args.prompt_len, jnp.int32)
        tables = np.zeros((batch, pages_per_seq), np.int32)
        for b in range(batch):
            tables[b] = np.arange(1 + b * pages_per_seq,
                                  1 + (b + 1) * pages_per_seq)
        page_tables = jnp.asarray(tables)
        cache = create_kv_cache(arch, num_pages, page_size, kv_dt)
        log(f"[{impl}] batch {batch}: {num_pages} pages "
            f"({2 * cache.k.nbytes / 2**30:.2f} GiB kv)")
        prefill = jax.jit(model.prefill, donate_argnums=(1,))
        t0 = time.monotonic()
        cache, logits, _ = prefill(params, cache, tokens, true_lens,
                                   page_tables)
        jax.block_until_ready(logits)
        prefill_time = time.monotonic() - t0
        log(f"[{impl}] prefill (compile+run): {prefill_time:.1f}s")

        def decode_loop(params, cache, first_tokens, page_tables):
            def body(carry, i):
                cache, toks, pos = carry
                cache, lg = model.decode(params, cache, toks, pos,
                                         page_tables)
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                return (cache, nxt, pos + 1), nxt

            pos0 = jnp.full((first_tokens.shape[0],), args.prompt_len,
                            jnp.int32)
            (cache, _, _), out = jax.lax.scan(
                body, (cache, first_tokens, pos0), jnp.arange(steps))
            return cache, out

        decode_jit = jax.jit(decode_loop, donate_argnums=(1,))
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t0 = time.monotonic()
        cache, out = decode_jit(params, cache, first, page_tables)
        jax.block_until_ready(out)
        log(f"[{impl}] decode loop compile+warmup: {time.monotonic() - t0:.1f}s")

        # timed runs (cache keeps advancing; positions restart per run
        # which re-measures the same window — steady state).  Between
        # runs the host gap (ready -> next dispatch) is the raw-path
        # analogue of the engine loop's dispatch_gap span: the bubble
        # the device sits idle while the host turns the loop around.
        best = 0.0
        run_wall = 0.0
        host_gaps = []
        t_ready = None
        for r in range(args.repeats):
            t0 = time.monotonic()
            if t_ready is not None:
                host_gaps.append(t0 - t_ready)
            cache, out = decode_jit(params, cache, first, page_tables)
            jax.block_until_ready(out)
            t_ready = time.monotonic()
            dt = t_ready - t0
            run_wall += dt
            tps = batch * steps / dt
            log(f"[{impl}] run {r}: {dt * 1e3:.1f} ms -> {tps:.0f} tok/s")
            best = max(best, tps)

        if host_gaps and run_wall > 0.0:
            gap_total = sum(host_gaps)
            gap_stats = (100.0 * gap_total / (run_wall + gap_total),
                         1e3 * gap_total / len(host_gaps))
        else:       # single repeat: no inter-dispatch window to measure
            gap_stats = (0.0, 0.0)
        return best, gap_stats

    def measure_ttft(model):
        """Steady-state single-request TTFT: warm batch-1 prefill +
        first-token logits (BASELINE.md asks p50 TTFT < 200 ms)."""
        rng = np.random.RandomState(0)
        t1 = jnp.asarray(
            rng.randint(0, arch.vocab_size, (1, args.prompt_len)), jnp.int32)
        tl1 = jnp.full((1,), args.prompt_len, jnp.int32)
        pt1 = jnp.arange(1, 1 + pages_per_seq, dtype=jnp.int32)[None]
        prefill1 = jax.jit(model.prefill, donate_argnums=(1,))
        cache1 = create_kv_cache(arch, pages_per_seq + 1, page_size, kv_dt)
        cache1, lg1, _ = prefill1(params, cache1, t1, tl1, pt1)  # compile
        jax.block_until_ready(lg1)
        ttfts = []
        for _ in range(max(args.repeats, 3)):
            cache1 = create_kv_cache(arch, pages_per_seq + 1, page_size,
                                     kv_dt)
            t0 = time.monotonic()
            cache1, lg1, _ = prefill1(params, cache1, t1, tl1, pt1)
            jax.block_until_ready(lg1)
            ttfts.append(time.monotonic() - t0)
        return sorted(ttfts)[len(ttfts) // 2] * 1e3

    for i, batch in enumerate(batch_ladder):
        try:
            best, gap_stats = run_path(attn_impl, model, batch)
            break
        except Exception as e:
            # the ladder walks down on HBM exhaustion only; anything
            # else — a kernel that fails to compile included — is an
            # error of this phase
            if ("RESOURCE_EXHAUSTED" not in str(e)
                    or i + 1 == len(batch_ladder)):
                raise
            log(f"batch {batch} exhausted HBM; retrying at "
                f"{batch_ladder[i + 1]}")

    ttft_ms = measure_ttft(model)
    log(f"steady TTFT (batch-1 prefill, {args.prompt_len} tokens): "
        f"{ttft_ms:.1f} ms")

    suffix = f"_{args.quant}" if args.quant else ""
    if args.kv_dtype == "int8":
        suffix += "_kvint8"
    result = {
        "metric": f"{model_name}{suffix}_decode_throughput",
        "value": round(best, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(best / BASELINE_TOK_S, 3),
        "batch": batch,
        "platform": platform,
        "attn_impl": attn_impl,
        "kv_dtype": ("int8" if args.kv_dtype == "int8"
                     else ("bfloat16" if on_tpu else "float32")),
        "device_idle_pct": round(gap_stats[0], 2),
        "dispatch_gap_ms": round(gap_stats[1], 3),
    }
    result.update(_devprof_pcts())
    result.update(_itl_metrics())
    result.update(_roofline_metrics(
        arch, best, batch, total_len, quant=args.quant,
        kv_dtype=args.kv_dtype, page_size=page_size))
    result["ttft_p50_ms"] = round(ttft_ms, 1)
    print(json.dumps(result), flush=True)


def phase_serve(args):
    jax = _init_jax(force_cpu=args.force_cpu)

    platform = jax.devices()[0].platform
    on_tpu = platform not in ("cpu",)
    model_name = args.model or ("phi-4-mini-instruct" if on_tpu
                                else "tiny-llama-test")
    spec_draft = args.spec_draft
    if spec_draft == "self":
        spec_draft = model_name
    res = bench_serving_path(model_name, on_tpu, quant=args.quant,
                             spec_ngram=args.spec_ngram,
                             spec_draft=spec_draft,
                             spec_temp=args.spec_temp)
    print(json.dumps(res), flush=True)


def phase_wquant_quality(args):
    """Weight-quant quality legs: serve the committed REAL checkpoints
    under each weight scheme and count golden prompts whose greedy
    continuation diverges from the pinned fp32 golden.  This is the
    quality half of the weight ladder — the throughput rows say int4 is
    faster, this row says what it costs (tests/test_weight_quant.py
    pins the same continuations exactly; here we just report counts).
    CPU-cheap: the checkpoints are ~5M-param byte LMs."""
    _init_jax(force_cpu=args.force_cpu)
    import glob as _glob

    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine, SamplingParams

    repo = os.path.dirname(os.path.abspath(__file__))
    testdata = os.path.join(repo, "tests", "testdata")
    models = sorted(
        os.path.basename(os.path.dirname(p))
        for p in _glob.glob(os.path.join(repo, "checkpoints", "*",
                                         "model.safetensors"))
        if os.path.exists(os.path.join(
            testdata,
            f"goldens_{os.path.basename(os.path.dirname(p))}.json")))
    if not models:
        print(json.dumps({"error": "no committed checkpoints"}), flush=True)
        return

    out = {"wquant_models": ",".join(models)}
    totals = {"int8": 0, "int4": 0}
    n_prompts = 0
    for model in models:
        golden = json.load(open(os.path.join(testdata,
                                             f"goldens_{model}.json")))
        n_prompts += len(golden["prompts"])
        for scheme in ("int8", "int4"):
            cfg = EngineConfig(
                model=model,
                weights_dir=os.path.join(repo, "checkpoints", model),
                dtype="float32", max_model_len=512, max_num_seqs=2,
                prefill_buckets=(64, 128), enable_prefix_caching=False,
                quantization=scheme, seed=0)
            eng = InferenceEngine(cfg)
            eng.start()
            try:
                for p in golden["prompts"]:
                    want = p["fp32"]["greedy_tokens"]
                    req = eng.submit(
                        list(p["prompt_tokens"]),
                        SamplingParams(max_tokens=len(want),
                                       temperature=0.0, ignore_eos=True))
                    if list(req.stream()) != want:
                        totals[scheme] += 1
            finally:
                eng.stop()
    out["wquant_prompts_total"] = n_prompts
    out["weight_int8_divergent_prompts"] = totals["int8"]
    out["weight_int4_divergent_prompts"] = totals["int4"]
    print(json.dumps(out), flush=True)


def phase_prefix(args):
    """Prefix-hit TTFT: cold vs warm submit of a shared-prefix prompt
    against the real engine with prefix caching ON — the latency delta
    EPP affinity routing banks on (docs/routing.md).  A warm hit skips
    the cached prefix's prefill compute entirely, so warm TTFT should
    sit well under cold."""
    jax = _init_jax(force_cpu=args.force_cpu)

    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine, SamplingParams
    from kaito_tpu.native import load_native

    if load_native() is None:
        print(json.dumps({"error": "prefix phase needs the native "
                                    "prefix cache (make native)"}),
              flush=True)
        return
    platform = jax.devices()[0].platform
    on_tpu = platform not in ("cpu",)
    model_name = args.model or ("phi-4-mini-instruct" if on_tpu
                                else "tiny-llama-test")
    if on_tpu:
        plen, max_len, dtype, buckets = 2048, 2560, "bfloat16", (2048,)
    else:
        plen, max_len, dtype, buckets = 192, 320, "float32", (256,)
    cfg = EngineConfig(model=model_name, dtype=dtype, kv_dtype=dtype,
                       max_num_seqs=2, max_model_len=max_len,
                       prefill_buckets=buckets, page_size=16,
                       enable_prefix_caching=True)
    eng = InferenceEngine(cfg)
    eng.start()
    try:
        vocab = eng.md.arch.vocab_size
        p = SamplingParams(max_tokens=2, temperature=0.0, ignore_eos=True)
        colds, warms = [], []
        for rep in range(max(args.repeats, 3)):
            # a fresh prefix per repeat: cold is genuinely cold
            prompt = np.random.RandomState(50 + rep).randint(
                1, min(vocab, 255), (plen,)).tolist()
            for sink in (colds, warms):
                t0 = time.monotonic()
                req = eng.submit(list(prompt), p)
                first = next(iter(req.stream()), None)
                if first is not None:
                    sink.append((time.monotonic() - t0) * 1e3)
                for _ in req.stream():
                    pass
        cold = sorted(colds)[len(colds) // 2]
        warm = sorted(warms)[len(warms) // 2]
        out = {
            "prefix_cold_ttft_ms": round(cold, 1),
            "prefix_warm_ttft_ms": round(warm, 1),
            "prefix_ttft_speedup": round(cold / warm, 2) if warm else 0.0,
            "prefix_cached_tokens":
                eng.counters["prefix_cached_tokens_total"],
            "prefix_hits": eng.counters.get("prefix_cache_hits_total", 0),
        }
    finally:
        eng.stop()
    log(f"[prefix] cold {out['prefix_cold_ttft_ms']} ms -> warm "
        f"{out['prefix_warm_ttft_ms']} ms "
        f"({out['prefix_cached_tokens']} cached tokens)")
    print(json.dumps(out), flush=True)


def phase_prefill_burst(args):
    """Concurrent-arrival prefill burst: N short+long prompts submitted
    at once, TTFT p50/p99 and prompt tokens per prefill dispatch, pack
    ON vs OFF (docs/prefill.md).  The tokens/dispatch ratio is the
    direct proxy for the packing win — serial runs one staged prompt
    per round regardless of budget headroom."""
    jax = _init_jax(force_cpu=args.force_cpu)

    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine, SamplingParams

    on_tpu = jax.devices()[0].platform not in ("cpu",)
    model_name = args.model or ("phi-4-mini-instruct" if on_tpu
                                else "tiny-llama-test")
    if on_tpu:
        short, long_, max_len, dtype = 256, 1024, 2048, "bfloat16"
        buckets, n_reqs, budget = (256, 512, 1024, 2048), 16, 2048
    else:
        short, long_, max_len, dtype = 24, 96, 256, "float32"
        buckets, n_reqs, budget = (32, 64, 128), 8, 256

    def run(pack):
        cfg = EngineConfig(model=model_name, dtype=dtype, kv_dtype=dtype,
                           max_num_seqs=n_reqs, max_model_len=max_len,
                           prefill_buckets=buckets, page_size=16,
                           max_prefill_tokens=budget,
                           enable_prefix_caching=False,
                           prefill_pack=pack, seed=0)
        eng = InferenceEngine(cfg)
        eng.start()
        try:
            vocab = eng.md.arch.vocab_size
            p = SamplingParams(max_tokens=4, temperature=0.0,
                               ignore_eos=True)
            rng = np.random.RandomState(11)
            prompts = [rng.randint(
                1, min(vocab, 255),
                (long_ if i % 3 == 0 else short,)).tolist()
                for i in range(n_reqs)]
            subs, reqs = [], []
            for pr in prompts:
                subs.append(time.monotonic())
                reqs.append(eng.submit(list(pr), p))
            for r in reqs:
                for _ in r.stream():
                    pass
            ttfts = sorted((r.first_token_time - t) * 1e3
                           for r, t in zip(reqs, subs)
                           if r.first_token_time is not None)
            steps = max(1, eng.counters["prefill_steps_total"])
            return {
                "ttft_p50_ms": round(ttfts[len(ttfts) // 2], 1),
                "ttft_p99_ms": round(ttfts[
                    min(len(ttfts) - 1,
                        int(len(ttfts) * 0.99))], 1),
                "tokens_per_dispatch": round(
                    eng.counters["prefill_tokens_total"] / steps, 1),
                "dispatches": steps,
            }
        finally:
            eng.stop()

    serial = run(1)
    packed = run(0)
    out = {"prefill_burst_requests": n_reqs}
    out.update(_devprof_pcts())
    for k, v in serial.items():
        out[f"prefill_serial_{k}"] = v
    for k, v in packed.items():
        out[f"prefill_pack_{k}"] = v
    out["prefill_pack_dispatch_speedup"] = round(
        packed["tokens_per_dispatch"] / serial["tokens_per_dispatch"], 2) \
        if serial["tokens_per_dispatch"] else 0.0
    out["prefill_pack_ttft_p50_speedup"] = round(
        serial["ttft_p50_ms"] / packed["ttft_p50_ms"], 2) \
        if packed["ttft_p50_ms"] else 0.0
    log(f"[prefill_burst] serial {serial['tokens_per_dispatch']} tok/"
        f"dispatch -> packed {packed['tokens_per_dispatch']} "
        f"({out['prefill_pack_dispatch_speedup']}x); ttft p50 "
        f"{serial['ttft_p50_ms']} -> {packed['ttft_p50_ms']} ms")
    print(json.dumps(out), flush=True)


def phase_int8_8b(args):
    """int8 8B-class on-chip serving: the reference's --quantization
    surface at the 8B scale a 16 GiB chip actually needs it for."""
    jax = _init_jax(force_cpu=args.force_cpu)

    on_tpu = jax.devices()[0].platform not in ("cpu",)
    res = bench_serving_path("llama-3.1-8b-instruct", on_tpu, quant="int8")
    print(json.dumps(res), flush=True)


def phase_cp(args):
    """Context-parallel prefill scaling on a virtual 8-device mesh
    (always CPU: the ring needs >= 2 devices and the box has one chip).
    Measures single-shot ring prefill wall-clock at seq=2/4 against the
    chunked baseline at the same prompt length, and checks greedy
    parity across all three engines.  On a 1-core host the virtual
    devices share the core, so wall-clock mainly reflects dispatch/
    gather overheads — per-chip attention workspace and FLOPs scale
    1/seq by construction (the real-hardware win; SURVEY §7(e))."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    _init_jax(force_cpu=True)

    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine, SamplingParams

    T = args.cp_tokens
    base = dict(model="tiny-llama-test", max_model_len=T + 64, page_size=16,
                max_num_seqs=2, dtype="float32", kv_dtype="float32",
                prefill_buckets=(512, T), seed=0, max_prefill_tokens=512,
                cp_min_tokens=256, enable_prefix_caching=False)
    prompt = [int(x) for x in
              np.random.RandomState(0).randint(2, 2000, size=T - 8)]
    p = SamplingParams(max_tokens=1, temperature=0.0, ignore_eos=True)
    out: dict = {"cp_tokens": T}
    if args.cp_attn_only:
        # attention-critical-path only (the >=32k leg): a full
        # chunked-prefill engine run at 32k takes tens of minutes on
        # this host, but the ring's per-chip shard attention — the
        # quantity that bounds TTFT on real hardware — measures in
        # seconds.  Query-chunked so the score tile stays bounded
        # ([1,H,QCH,T] instead of [1,H,T,T]) at long T.
        import jax
        import jax.numpy as jnp

        H, D, QCH = 4, 32, 2048
        NEG = -1e30
        rng = np.random.RandomState(1)

        @jax.jit
        def attn_chunk(q, k, v, offset):
            s = jnp.einsum("bthd,bshd->bhts", q, k,
                           preferred_element_type=jnp.float32)
            tq = offset + jnp.arange(q.shape[1])[:, None]
            tk = jnp.arange(k.shape[1])[None, :]
            s = jnp.where(tk <= tq, s, NEG)
            pr = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhts,bshd->bthd", pr.astype(v.dtype), v)

        k_full = jnp.asarray(rng.randn(1, T, H, D), jnp.float32)
        v_full = jnp.asarray(rng.randn(1, T, H, D), jnp.float32)
        for sp in (1, 2, 4):
            Tq = T // sp
            q = jnp.asarray(rng.randn(1, Tq, H, D), jnp.float32)
            for _warm in range(2):
                t0 = time.monotonic()
                for c0 in range(0, Tq, QCH):
                    attn_chunk(q[:, c0:c0 + QCH], k_full, v_full,
                               jnp.int32(T - Tq + c0)).block_until_ready()
                dt = time.monotonic() - t0
            out[f"cp_attn_ms_per_chip_seq{sp}"] = round(dt * 1e3, 1)
            log(f"cp attn-only seq{sp}: {dt * 1e3:.0f} ms")
        if out.get("cp_attn_ms_per_chip_seq4"):
            out["cp_per_chip_speedup_seq4"] = round(
                out["cp_attn_ms_per_chip_seq1"]
                / out["cp_attn_ms_per_chip_seq4"], 2)
        print(json.dumps(out), flush=True)
        return
    ref = None
    for name, sp in (("chunked", 1), ("seq2", 2), ("seq4", 4)):
        eng = InferenceEngine(EngineConfig(**base, sequence_parallel=sp))
        eng.start()
        try:
            for _warm in range(2):   # second run is compile-free
                t0 = time.monotonic()
                toks = list(eng.submit(list(prompt), p).stream())
                dt = time.monotonic() - t0
            if sp > 1 and eng.counters["prefill_steps_total"] != 2:
                out["error"] = f"{name}: CP path did not engage"
            if ref is None:
                ref = toks
            elif toks != ref:
                out["error"] = f"{name}: greedy output diverged"
        finally:
            eng.stop()
        out[f"cp_prefill_ms_{name}"] = round(dt * 1e3, 1)
        log(f"cp phase {name}: {dt * 1e3:.0f} ms")
    out["cp_parity"] = "error" not in out
    if out.get("cp_prefill_ms_seq4"):
        out["cp_speedup_seq4_vs_chunked"] = round(
            out["cp_prefill_ms_chunked"] / out["cp_prefill_ms_seq4"], 2)

    # per-chip critical path: the LAST ring shard attends all earlier
    # KV blocks, so its attention time is what bounds TTFT on real
    # hardware (collectives overlap the block matmuls).  Timed on ONE
    # device, so the 1/seq scaling here is a true measurement even on
    # this single-core host.
    from functools import partial

    import jax
    import jax.numpy as jnp

    H, D = 8, 32
    rng = np.random.RandomState(1)
    NEG = -1e30

    @partial(jax.jit, static_argnames=("offset",))
    def shard_attn(q, k, v, *, offset: int):
        s = jnp.einsum("bthd,bshd->bhts", q, k,
                       preferred_element_type=jnp.float32)
        tq = offset + jnp.arange(q.shape[1])[:, None]
        tk = jnp.arange(k.shape[1])[None, :]
        s = jnp.where(tk <= tq, s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), v)

    k_full = jnp.asarray(rng.randn(1, T, H, D), jnp.float32)
    v_full = jnp.asarray(rng.randn(1, T, H, D), jnp.float32)
    for sp in (1, 2, 4):
        Tq = T // sp
        q = jnp.asarray(rng.randn(1, Tq, H, D), jnp.float32)
        for _warm in range(2):
            t0 = time.monotonic()
            shard_attn(q, k_full, v_full,
                       offset=T - Tq).block_until_ready()
            dt = time.monotonic() - t0
        out[f"cp_attn_ms_per_chip_seq{sp}"] = round(dt * 1e3, 1)
    if out.get("cp_attn_ms_per_chip_seq4"):
        out["cp_per_chip_speedup_seq4"] = round(
            out["cp_attn_ms_per_chip_seq1"]
            / out["cp_attn_ms_per_chip_seq4"], 2)
    print(json.dumps(out), flush=True)


def phase_multichip(args):
    """Multi-chip decode ladder on the virtual 8-device mesh (always
    CPU: the ring needs >= 2 devices and the box has one chip).  Rows:
    single-chip baseline, tp=2 with the comm-overlap gate off and on
    (the A-B leg for docs/multichip.md), and pp=2.  Each row carries
    the schema-stable device-time attribution columns (comm_pct /
    overlap_pct, 0.0 when the profiler has no sample) plus one
    overlap_speedup column — on CPU the virtual devices share the core
    so the speedup mainly proves the gate's plumbing and parity; the
    latency win needs real ICI."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    _init_jax(force_cpu=True)

    import threading

    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine, SamplingParams

    steps = min(args.decode_steps or 64, 64)
    base = dict(model="tiny-llama-test", max_model_len=256, page_size=16,
                max_num_seqs=2, dtype="float32", kv_dtype="float32",
                prefill_buckets=(32,), seed=0,
                devprof_interval_s=3600.0,   # sampled manually per row
                devprof_window_s=0.25)
    prompt = [5, 6, 7, 8]
    p = SamplingParams(max_tokens=steps, temperature=0.0, ignore_eos=True)
    rows = (("tp1", {}),
            ("tp2_off", dict(tensor_parallel=2, comm_overlap=False)),
            ("tp2_on", dict(tensor_parallel=2, comm_overlap=True)),
            ("pp2", dict(pipeline_parallel=2)))
    out: dict = {}
    toks_by_row: dict = {}
    for name, extra in rows:
        try:
            eng = InferenceEngine(EngineConfig(**base, **extra))
        except Exception as e:   # a broken layout costs its row only
            out.setdefault("multichip_errors", []).append(f"{name}: {e}")
            continue
        eng.start()
        try:
            for _warm in range(2):   # second run is compile-free
                t0 = time.monotonic()
                toks = list(eng.submit(list(prompt), p).stream())
                dt = time.monotonic() - t0
            if len(toks) != steps:
                out.setdefault("multichip_errors", []).append(
                    f"{name}: decode produced {len(toks)}/{steps} tokens")
                continue
            toks_by_row[name] = toks
            # one profiler window around a burn decode, AFTER the timed
            # run (sampling perturbs the number being measured) -> real
            # comm attribution where the backend traces collectives
            if eng.devprof is not None:
                def _burn():
                    for _ in eng.submit(list(prompt), p).stream():
                        pass

                t = threading.Thread(target=_burn)
                t.start()
                eng.devprof.sample_window()
                t.join()
        finally:
            eng.stop()
        out[f"multichip_decode_tok_s_{name}"] = round(steps / dt, 1)
        pcts = _devprof_pcts(eng)
        out[f"multichip_comm_pct_{name}"] = pcts["comm_pct"]
        out[f"multichip_overlap_pct_{name}"] = pcts["overlap_pct"]
        log(f"multichip {name}: {steps / dt:.1f} tok/s "
            f"comm={pcts['comm_pct']}% overlap={pcts['overlap_pct']}%")
    parity = ("tp1" in toks_by_row
              and all(t == toks_by_row["tp1"]
                      for t in toks_by_row.values()))
    out["multichip_parity"] = bool(parity)
    if not parity:
        out["error"] = "multichip: greedy output diverged across rows"
    on = out.get("multichip_decode_tok_s_tp2_on", 0.0)
    off = out.get("multichip_decode_tok_s_tp2_off", 0.0)
    out["multichip_overlap_speedup"] = (round(on / off, 2)
                                        if on and off else 0.0)
    print(json.dumps(out), flush=True)


def phase_pd(args):
    """P/D disaggregation hand-off: measure KV-transfer latency from a
    prefill engine to a decode engine at 2k/8k contexts (chunked,
    overlapped path in engine/pd.py; reference contract is the NIXL
    connector hand-off, inference_api.py)."""
    jax = _init_jax(force_cpu=args.force_cpu)

    from kaito_tpu.engine.pd import bench_kv_handoff

    on_tpu = jax.devices()[0].platform not in ("cpu",)
    model_name = args.model or ("phi-4-mini-instruct" if on_tpu
                                else "tiny-llama-test")
    ctxs = (2048, 8192) if on_tpu else (128,)
    res = bench_kv_handoff(model_name, ctxs, on_tpu)
    print(json.dumps(res), flush=True)


def phase_kvpool(args):
    """Cluster KV pool (docs/kv-pool.md): time an ACTUAL chunked prefix
    transfer between two live engine servers — A serves a prompt and
    publishes its prefix pages, B is handed the EPP-style fetch headers
    and pulls them over the wire instead of recomputing.  Reports the
    measured transfer alongside the static transfer-cost prior as
    ``transfer_cost_model_error``: that prior is what every
    route-vs-fetch decision eats before a replica has EWMA samples, so
    its error IS the quality of cold-start fetch decisions."""
    jax = _init_jax(force_cpu=args.force_cpu)
    import urllib.request

    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine
    from kaito_tpu.engine.pd import transfer_cost
    from kaito_tpu.engine.server import make_server

    on_tpu = jax.devices()[0].platform not in ("cpu",)
    model_name = args.model or "tiny-llama-test"
    cfg = EngineConfig(
        model=model_name, max_model_len=512, page_size=16, max_num_seqs=2,
        dtype="bfloat16" if on_tpu else "float32",
        kv_dtype=args.kv_dtype or ("bfloat16" if on_tpu else "float32"),
        prefill_buckets=(128, 256), seed=0, kv_pool_enabled=True)

    def boot():
        eng = InferenceEngine(cfg)
        eng.start()
        srv = make_server(eng, cfg, host="127.0.0.1", port=0)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return eng, srv, f"http://127.0.0.1:{srv.server_address[1]}"

    def post(url, body, headers=None):
        req = urllib.request.Request(
            url + "/v1/completions", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json", **(headers or {})})
        return json.loads(urllib.request.urlopen(req, timeout=120).read())

    a_eng, a_srv, a_url = boot()
    b_eng, b_srv, b_url = boot()
    out: dict = {"kvpool_model": model_name}
    try:
        # warm A: the finished request publishes its prefix pages
        prompt = "cluster kv pool transfer bench " * 12
        post(a_url, {"prompt": prompt, "max_tokens": 4,
                     "temperature": 0.0})
        with urllib.request.urlopen(a_url + "/debug/kv_pool",
                                    timeout=10) as r:
            advert = json.loads(r.read())
        if not advert.get("entries"):
            out["error"] = "kvpool: replica A published no prefix entry"
            print(json.dumps(out), flush=True)
            return
        key = advert["entries"][0]["key"]
        # B fetches: same prompt + the headers the EPP would inject
        t0 = time.monotonic()
        post(b_url, {"prompt": prompt, "max_tokens": 4,
                     "temperature": 0.0},
             headers={"X-Kaito-KV-Fetch": a_url,
                      "X-Kaito-KV-Fetch-Key": key})
        warm_e2e_s = time.monotonic() - t0
        fetches = b_eng.counters["kv_pool_fetches_total"]
        n_tokens = b_eng.counters["kv_pool_fetched_tokens_total"]
        snap = b_eng.pd_costs.snapshot()
        if fetches < 1 or not snap.get("net_bytes_s"):
            out["error"] = "kvpool: no cross-replica fetch happened"
            print(json.dumps(out), flush=True)
            return
        kv_itemsize = b_eng.cache.k.dtype.itemsize
        scale_bpt = 0.0
        if getattr(b_eng.cache, "k_scale", None) is not None:
            arch = b_eng.md.arch
            scale_bpt = (8.0 * arch.num_layers * arch.num_kv_heads
                         / max(1, cfg.page_size))
        modeled = transfer_cost(n_tokens, b_eng.md.arch, kv_itemsize,
                                scale_bytes_per_token=scale_bpt)
        # one transfer sample -> the EWMA is exactly bytes/seconds of
        # the pull we just timed; scoring the prior against the same
        # byte volume isolates BANDWIDTH error from byte-count error
        measured_s = modeled["kv_bytes"] / snap["net_bytes_s"]
        out.update({
            "kvpool_fetch_tokens": int(n_tokens),
            "kvpool_kv_bytes": int(modeled["kv_bytes"]),
            "kvpool_measured_transfer_s": measured_s,
            "kvpool_modeled_transfer_s": modeled["transfer_s"],
            "kvpool_measured_net_bytes_s": snap["net_bytes_s"],
            "kvpool_warm_e2e_s": warm_e2e_s,
            "transfer_cost_model_error":
                abs(modeled["transfer_s"] - measured_s)
                / max(measured_s, 1e-9),
        })
        print(json.dumps(out), flush=True)
    finally:
        for s in (a_srv, b_srv):
            s.shutdown()
        a_eng.stop()
        b_eng.stop()


def phase_conversation(args):
    """Multi-turn conversation replay (docs/kv-pool.md "Tier 3: SSD"):
    one live engine with the disk tier on replays a conversation —
    turn 1 cold-prefills the history, turn 2 (history + new message)
    imports the turn-1 prefix from the HOST pool store, then the host
    store is squeezed so the conversation demotes to SSD and turn 3
    imports the same prefix from DISK.  Reports per-turn TTFT and the
    per-tier hit split: the whole point of the tier is that turn-N
    TTFT stays below turn-1 even after the conversation leaves RAM."""
    jax = _init_jax(force_cpu=args.force_cpu)
    import shutil
    import tempfile
    import urllib.request

    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine
    from kaito_tpu.engine.server import make_server

    on_tpu = jax.devices()[0].platform not in ("cpu",)
    model_name = args.model or "tiny-llama-test"
    disk_dir = tempfile.mkdtemp(prefix="kaito-kv-bench-")
    cfg = EngineConfig(
        model=model_name, max_model_len=1024, page_size=16, max_num_seqs=2,
        dtype="bfloat16" if on_tpu else "float32",
        kv_dtype=args.kv_dtype or ("bfloat16" if on_tpu else "float32"),
        prefill_buckets=(128, 512, 1024), seed=0, kv_pool_enabled=True,
        kv_pool_disk_bytes=1 << 30, kv_pool_disk_dir=disk_dir)
    eng = InferenceEngine(cfg)
    eng.start()
    srv = make_server(eng, cfg, host="127.0.0.1", port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"

    def post(body):
        req = urllib.request.Request(
            url + "/v1/completions", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        return json.loads(urllib.request.urlopen(req, timeout=120).read())

    out: dict = {"conversation_model": model_name}
    try:
        # every unit is EXACTLY 30 chars (byte-level tokenizer keeps
        # turn lengths in the same compile bucket across replays)
        history = "conversation history filler x " * 28
        suffix = "then one new user question ab "
        compile_hist = "warmup compile bucket filler x" * 28
        # pre-compile the long-prefill bucket, then the import +
        # short-remainder programs via a sacrificial conversation
        post({"prompt": compile_hist, "max_tokens": 1, "temperature": 0.0})
        post({"prompt": compile_hist + suffix, "max_tokens": 1,
              "temperature": 0.0})
        # turn 1: cold full prefill of the history
        t0 = time.monotonic()
        post({"prompt": history, "max_tokens": 1, "temperature": 0.0})
        turn1_s = time.monotonic() - t0
        # turn 2: history + new message -> host-tier import
        t0 = time.monotonic()
        post({"prompt": history + suffix, "max_tokens": 1,
              "temperature": 0.0})
        turn2_s = time.monotonic() - t0
        # squeeze the host store to ~1.2 average entries: the budget
        # still ADMITS the equal-length evictor (put() refuses an
        # entry bigger than the whole budget without evicting) but its
        # publish forces every resident entry out, and the spill
        # worker demotes the conversation to SSD
        evictor = "unrelated talk pushing it out " * 28
        resident = max(1, len(eng.kv_pool))
        eng.kv_pool.max_bytes = max(
            1, int(eng.kv_pool.used_bytes / resident * 1.2))
        post({"prompt": evictor, "max_tokens": 1, "temperature": 0.0})
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if eng.kv_tier.spills_total >= resident:
                break
            time.sleep(0.05)
        # turn 3: the replayed conversation now imports from DISK
        t0 = time.monotonic()
        post({"prompt": history + suffix, "max_tokens": 1,
              "temperature": 0.0})
        turn3_s = time.monotonic() - t0
        snap = eng.pd_costs.snapshot()
        out.update({
            "conversation_turn1_ttft_s": turn1_s,
            "conversation_turn2_ttft_s": turn2_s,
            "conversation_turn3_ttft_s": turn3_s,
            "conversation_turn3_vs_turn1": turn3_s / max(turn1_s, 1e-9),
            "conversation_host_hits":
                float(eng.counters["kv_tier_host_hits_total"]),
            "conversation_disk_hits":
                float(eng.counters["kv_tier_disk_hits_total"]),
            "conversation_import_tokens":
                float(eng.counters["kv_tier_import_tokens_total"]),
            "conversation_disk_read_bytes_s":
                float(snap.get("disk_bytes_s") or 0.0),
        })
        if eng.counters["kv_tier_disk_hits_total"] < 1:
            out["error"] = "conversation: turn 3 never hit the disk tier"
        print(json.dumps(out), flush=True)
    finally:
        srv.shutdown()
        eng.stop()
        shutil.rmtree(disk_dir, ignore_errors=True)


def phase_lora(args):
    """Multi-LoRA serving (docs/multi-lora.md): hot-load latency into
    the HBM slot table, the zero-retrace pin across the load, base vs
    adapter vs heterogeneous-batch decode throughput (the slot-gather
    overhead), and host-tier fault-back-in latency after an eviction.
    Runs on the tiny test model: the adapter path's costs are the slot
    table and gather, not model FLOPs."""
    _init_jax(force_cpu=args.force_cpu)
    import shutil
    import tempfile
    import urllib.request

    import jax as _jax
    import jax.numpy as jnp

    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine
    from kaito_tpu.engine.model import TransformerLM
    from kaito_tpu.engine.server import make_server
    from kaito_tpu.models import get_model_by_name
    from kaito_tpu.tuning.lora import LoraConfig, add_lora_params, save_adapter

    arch = get_model_by_name("tiny-llama-test").arch
    root = tempfile.mkdtemp(prefix="kaito-lora-bench-")

    def make_adapter(name, seed, r=8):
        model = TransformerLM(arch, dtype=jnp.float32)
        params = add_lora_params(
            model, model.init_params(_jax.random.PRNGKey(0)),
            LoraConfig(r=r), _jax.random.PRNGKey(seed))
        save_adapter(os.path.join(root, name), params, LoraConfig(r=r),
                     "tiny-llama-test")

    for i, name in enumerate(("bench-a", "bench-b", "bench-c")):
        make_adapter(name, seed=i + 1)

    cfg = EngineConfig(model="tiny-llama-test", max_model_len=256,
                       page_size=16, max_num_seqs=4, dtype="float32",
                       kv_dtype="float32", prefill_buckets=(64,), seed=0,
                       adapter_slots=2, adapter_rmax=8)
    eng = InferenceEngine(cfg)
    eng.start()
    srv = make_server(eng, cfg, host="127.0.0.1", port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"

    def post(path, body):
        req = urllib.request.Request(
            url + path, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        return json.loads(urllib.request.urlopen(req, timeout=120).read())

    def completion_tok_s(model_field, n=64):
        t0 = time.monotonic()
        post("/v1/completions", {"model": model_field,
                                 "prompt": "adapter bench " * 8,
                                 "max_tokens": n, "temperature": 0.0})
        return n / (time.monotonic() - t0)

    out: dict = {}
    try:
        completion_tok_s("tiny-llama-test", 16)      # warm the jit cache
        traces0 = eng._decode_fn._cache_size()
        t0 = time.monotonic()
        post("/v1/adapters", {"name": "bench-a",
                              "source": os.path.join(root, "bench-a")})
        out["lora_hot_load_s"] = time.monotonic() - t0
        out["lora_base_tok_s"] = completion_tok_s("tiny-llama-test")
        out["lora_adapter_tok_s"] = completion_tok_s("bench-a")
        # heterogeneous batch: base + adapter decoding concurrently
        t0 = time.monotonic()
        threads = [threading.Thread(target=completion_tok_s, args=(m,))
                   for m in ("tiny-llama-test", "bench-a")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        out["lora_hetero_tok_s"] = 128 / (time.monotonic() - t0)
        # fill both slots, demoting bench-a to the host tier ...
        for name in ("bench-b", "bench-c"):
            post("/v1/adapters", {"name": name,
                                  "source": os.path.join(root, name)})
        def snapshot():
            with urllib.request.urlopen(url + "/v1/adapters",
                                        timeout=10) as r:
                return json.loads(r.read())

        out["lora_host_tier"] = snapshot()["host_tier"]
        # ... then time the fault-back-in on the request path
        t0 = time.monotonic()
        completion_tok_s("bench-a", 4)
        out["lora_fault_in_e2e_s"] = time.monotonic() - t0
        out["lora_faults_total"] = snapshot()["faults_total"]
        out["lora_retraces"] = eng._decode_fn._cache_size() - traces0
        print(json.dumps(out), flush=True)
    finally:
        srv.shutdown()
        eng.stop()
        shutil.rmtree(root, ignore_errors=True)


def phase_structured(args):
    """Grammar-constrained decoding (docs/structured-output.md):
    constrained-vs-free decode throughput (the per-step mask gather),
    cold-vs-warm first-token latency (grammar compile off the hot
    path), and the n-gram spec accept rate with constraints on — the
    composition invariant is that constrained requests keep
    speculating.  Tiny test model: the costs measured are the grammar
    table and mask path, not model FLOPs."""
    _init_jax(force_cpu=args.force_cpu)
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine, SamplingParams
    from kaito_tpu.engine.grammar import GrammarSpec, canonical_schema

    cfg = EngineConfig(model="tiny-llama-test", max_model_len=256,
                       page_size=16, max_num_seqs=4, dtype="float32",
                       kv_dtype="float32", prefill_buckets=(64,), seed=0,
                       enable_prefix_caching=False, speculative_ngram=4)
    eng = InferenceEngine(cfg)
    # schema-stable output: every field present even when a leg
    # degenerates (accept rate reads 0.0 when speculation never fires)
    out = {"structured_free_tok_s": 0.0,
           "structured_constrained_tok_s": 0.0,
           "structured_cold_first_token_s": 0.0,
           "structured_warm_first_token_s": 0.0,
           "structured_spec_accept_rate": 0.0}
    schema = {"type": "object",
              "properties": {"ok": {"type": "boolean"},
                             "tags": {"type": "array",
                                      "items": {"enum": ["a", "b"]},
                                      "maxItems": 8},
                             "id": {"type": "string", "maxLength": 8}},
              "required": ["ok", "tags", "id"]}

    def run_one(grammar, prompt, n=48):
        t0 = time.monotonic()
        r = eng.submit(list(prompt), SamplingParams(
            max_tokens=n, temperature=0.0,
            ignore_eos=grammar is None, grammar=grammar))
        first = None
        for _ in range(1200):
            if r.finish_reason:
                break
            eng.step()
            if first is None and r.output_tokens:
                first = time.monotonic() - t0
        dt = time.monotonic() - t0
        return len(r.output_tokens) / dt, first or dt

    try:
        run_one(None, (1, 2, 3), n=8)              # warm the jit cache
        spec = GrammarSpec("json_schema", canonical_schema(schema))

        def first_token_s(prompt):
            # compile/cache-lookup + admission + prefill + first emit,
            # exactly what a server request pays before its first delta
            t0 = time.monotonic()
            g = eng.grammar_cache.get(spec, eng.tokenizer)
            t_compile = time.monotonic() - t0
            tok_s, first = run_one(g, prompt)
            return t_compile + first, tok_s

        cold, _ = first_token_s((10, 20, 30))      # compile rides once
        warm, tok_s = first_token_s((11, 21, 31))  # cache hit
        out["structured_cold_first_token_s"] = round(cold, 6)
        out["structured_warm_first_token_s"] = round(warm, 6)
        out["structured_constrained_tok_s"] = round(tok_s, 2)
        free_tok_s, _ = run_one(None, (10, 20, 30))
        out["structured_free_tok_s"] = round(free_tok_s, 2)
        prop = eng.counters.get("spec_proposed_tokens_total", 0)
        acc = eng.counters.get("spec_accepted_tokens_total", 0)
        out["structured_spec_accept_rate"] = round(
            acc / prop, 4) if prop else 0.0
        print(json.dumps(out), flush=True)
    finally:
        pass


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", default="",
                    choices=["", "raw", "serve",
                             "int8_8b", "pd", "cp", "multichip", "prefix",
                             "prefill_burst", "kvpool", "conversation",
                             "lora", "structured", "wquant_quality"])
    ap.add_argument("--cp-tokens", type=int, default=8192)
    ap.add_argument("--cp-attn-only", action="store_true",
                    help="cp phase: measure only the per-chip shard-"
                         "attention critical path (the cheap >=32k leg)")
    ap.add_argument("--skip-cp-bench", action="store_true")
    ap.add_argument("--skip-multichip-bench", action="store_true")
    ap.add_argument("--spec-draft", default="",
                    help="draft preset for the speculative serve leg "
                         "('self' = the benched model drafts for "
                         "itself)")
    ap.add_argument("--spec-temp", type=float, default=0.0,
                    help="client sampling temperature for the serve "
                         "phase (draft speculation keeps sampled "
                         "traffic distribution-identical)")
    ap.add_argument("--spec-ngram", type=int, default=0,
                    help="serve phase: n-gram speculation window "
                         "(0 = off; the spec on/off ladder row)")
    ap.add_argument("--skip-spec-bench", action="store_true")
    ap.add_argument("--skip-prefix-bench", action="store_true")
    ap.add_argument("--skip-prefill-bench", action="store_true",
                    help="skip the packed-prefill burst leg "
                         "(docs/prefill.md)")
    ap.add_argument("--model", default="")
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--decode-steps", type=int, default=128)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--attn-impl", default="", choices=["", "jax", "pallas"])
    ap.add_argument("--quant", default="", choices=["", "int8", "int4"])
    ap.add_argument("--kv-dtype", default="",
                    choices=["", "bfloat16", "int8"],
                    help="KV page-pool dtype for the raw decode ladder "
                         "(int8 = quantized pages + fp32 page scales)")
    ap.add_argument("--skip-kv-int8", action="store_true",
                    help="skip the int8-KV decode comparison row")
    ap.add_argument("--skip-wquant", action="store_true",
                    help="skip the bf16-vs-int8-vs-int4 weight ladder "
                         "and its quality legs")
    ap.add_argument("--force-cpu", action="store_true")
    ap.add_argument("--skip-server-bench", action="store_true")
    ap.add_argument("--skip-int8-8b", action="store_true")
    ap.add_argument("--skip-pd-bench", action="store_true")
    ap.add_argument("--skip-conversation-bench", action="store_true",
                    help="skip the multi-turn conversation replay leg "
                         "over the KV tiers (docs/kv-pool.md); its "
                         "result keys stay present at 0.0")
    ap.add_argument("--skip-lora-bench", action="store_true",
                    help="skip the multi-LoRA hot-load/adapter-decode "
                         "legs (docs/multi-lora.md)")
    ap.add_argument("--skip-structured-bench", action="store_true",
                    help="skip the grammar-constrained decoding legs "
                         "(docs/structured-output.md)")
    ap.add_argument("--deadline", type=float, default=1500.0)
    args = ap.parse_args()

    if args.phase == "prefix":
        phase_prefix(args)
    elif args.phase == "prefill_burst":
        phase_prefill_burst(args)
    elif args.phase == "wquant_quality":
        phase_wquant_quality(args)
    elif args.phase == "raw":
        phase_raw(args)
    elif args.phase == "serve":
        phase_serve(args)
    elif args.phase == "int8_8b":
        phase_int8_8b(args)
    elif args.phase == "pd":
        phase_pd(args)
    elif args.phase == "kvpool":
        phase_kvpool(args)
    elif args.phase == "conversation":
        phase_conversation(args)
    elif args.phase == "lora":
        phase_lora(args)
    elif args.phase == "structured":
        phase_structured(args)
    elif args.phase == "cp":
        phase_cp(args)
    elif args.phase == "multichip":
        phase_multichip(args)
    else:
        orchestrate(args)


if __name__ == "__main__":
    main()
