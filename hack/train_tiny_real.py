"""Train the committed tiny-llama-real checkpoint.

A genuinely TRAINED (not synthetic) byte-level llama so the repo
carries an end-task regression anchor: golden logprobs + held-out
bits/byte pin rope/serving/quantization correctness the way the
reference pins model quality with published MT-Bench scores
(model_catalog_mtbench_scores.md) — no network required.

Corpus: English prose already in the image (site-packages METADATA /
README files), ~3 MB; last 2% held out for validation.  Training uses
the repo's own train step (kaito_tpu.tuning.make_train_step).

Run: python hack/train_tiny_real.py --steps 600
Outputs:
  checkpoints/tiny-llama-real/model.safetensors   (bf16)
  checkpoints/tiny-llama-real/training_report.json
"""

import argparse
import glob
import json
import os
import time

import sys

import jax

# default to CPU (deterministic, always available); pass --tpu to use
# the accelerator
if "--tpu" not in sys.argv:
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def build_corpus(max_bytes: int = 6_000_000) -> bytes:
    """Deterministic English-prose corpus from files baked into the
    image (package metadata/readmes), filtered to mostly-ASCII text."""
    paths = sorted(
        glob.glob("/opt/venv/lib/python3.12/site-packages/*.dist-info/METADATA")
        + glob.glob("/opt/venv/lib/python3.12/site-packages/*/README*"))
    chunks = []
    total = 0
    for p in paths:
        try:
            data = open(p, "rb").read()
        except OSError:
            continue
        if not data or data.count(0):
            continue
        printable = sum(1 for b in data if 32 <= b < 127 or b in (9, 10, 13))
        if printable / len(data) < 0.95:
            continue
        chunks.append(data)
        total += len(data)
        if total >= max_bytes:
            break
    corpus = b"\n\n".join(chunks)
    if len(corpus) < 500_000:
        raise SystemExit(f"corpus too small: {len(corpus)} bytes")
    return corpus


def batches(data: np.ndarray, batch: int, seqlen: int, rng: np.random.RandomState):
    n = len(data) - seqlen - 1
    while True:
        idx = rng.randint(0, n, size=(batch,))
        tok = np.stack([data[i:i + seqlen + 1] for i in idx])
        yield {"tokens": jnp.asarray(tok, jnp.int32),
               "mask": jnp.ones((batch, seqlen), jnp.float32)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seqlen", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--model", default="tiny-llama-real",
                    help="preset to train (e.g. tiny-moe-real)")
    ap.add_argument("--tpu", action="store_true",
                    help="train on the accelerator instead of CPU")
    args = ap.parse_args()
    out_dir = os.path.join(REPO, "checkpoints", args.model)

    import optax

    from kaito_tpu.engine.model import TransformerLM
    from kaito_tpu.engine.weights import export_hf_state_dict
    from kaito_tpu.models import get_model_by_name
    from kaito_tpu.tuning import TrainState, make_train_step

    corpus = build_corpus()
    split = int(len(corpus) * 0.98)
    train = np.frombuffer(corpus[:split], np.uint8).astype(np.int32)
    val = np.frombuffer(corpus[split:], np.uint8).astype(np.int32)
    print(f"corpus: {len(corpus) / 1e6:.2f} MB "
          f"(train {len(train) / 1e6:.2f}M, val {len(val) / 1e3:.0f}k bytes)",
          flush=True)

    md = get_model_by_name(args.model)
    model = TransformerLM(md.arch, dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0))
    sched = optax.warmup_cosine_decay_schedule(
        0.0, args.lr, warmup_steps=min(20, max(1, args.steps // 4)),
        decay_steps=args.steps, end_value=args.lr / 10)
    optimizer = optax.chain(optax.clip_by_global_norm(1.0),
                            optax.adamw(sched, weight_decay=0.01))
    state = TrainState(params=params, opt_state=optimizer.init(params),
                       step=jnp.zeros((), jnp.int32))
    step_fn = jax.jit(make_train_step(model, optimizer), donate_argnums=(0,))

    rng = np.random.RandomState(0)
    it = batches(train, args.batch, args.seqlen, rng)
    t0 = time.monotonic()
    for i in range(args.steps):
        state, metrics = step_fn(state, next(it))
        if i % 25 == 0 or i == args.steps - 1:
            loss = float(metrics["loss"])
            print(f"step {i:4d}  loss {loss:.4f} "
                  f"({loss / np.log(2):.3f} bits/byte)  "
                  f"{time.monotonic() - t0:.0f}s", flush=True)

    # held-out bits/byte over fixed random windows of the val slice
    from kaito_tpu.tuning.train_step import cross_entropy_loss

    @jax.jit
    def vloss(params, batch):
        logits = model.forward_train(params, batch["tokens"][:, :-1])
        return cross_entropy_loss(logits, batch["tokens"][:, 1:],
                                  batch["mask"])

    vrng = np.random.RandomState(1)
    vit = batches(val, args.batch, args.seqlen, vrng)
    vlosses = [float(vloss(state.params, next(vit))) for _ in range(8)]
    val_bpb = float(np.mean(vlosses) / np.log(2))
    print(f"held-out: {val_bpb:.3f} bits/byte", flush=True)

    os.makedirs(out_dir, exist_ok=True)
    from safetensors.numpy import save_file

    sd = export_hf_state_dict(model, state.params)
    sd = {k: np.asarray(v, np.dtype("bfloat16")) if v.dtype == np.float32
          else np.asarray(v) for k, v in sd.items()}
    save_file(sd, os.path.join(out_dir, "model.safetensors"))
    report = {
        "model": args.model,
        "params_m": round(sum(x.size for x in jax.tree.leaves(
            state.params)) / 1e6, 2),
        "corpus_bytes": len(corpus),
        "steps": args.steps,
        "batch": args.batch,
        "seqlen": args.seqlen,
        "final_train_loss_nats": float(metrics["loss"]),
        "heldout_bits_per_byte": round(val_bpb, 3),
        "tokenizer": "byte-level (vocab 258)",
    }
    with open(os.path.join(out_dir, "training_report.json"), "w") as f:
        json.dump(report, f, indent=2)
    print("saved", out_dir, flush=True)


if __name__ == "__main__":
    main()
