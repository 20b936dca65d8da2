"""Tuning job CLI — the in-pod entrypoint rendered by
``kaito_tpu.manifests.tuning_job`` (reference counterpart:
``accelerate launch ... fine_tuning.py`` with parsed dataclass args,
``presets/workspace/tuning/text-generation/{cli,parser}.py``)."""

from __future__ import annotations

import argparse
import logging

from kaito_tpu.tuning.lora import LoraConfig
from kaito_tpu.tuning.trainer import TrainConfig, Trainer


def parse_args(argv=None) -> TrainConfig:
    ap = argparse.ArgumentParser(prog="kaito-tpu-tune")
    ap.add_argument("--model", required=True)
    ap.add_argument("--method", default="lora", choices=["lora", "qlora", "full"])
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--output-dir", required=True)
    ap.add_argument("--mesh", default="")
    ap.add_argument("--config-file", default="")
    ap.add_argument("--learning-rate", type=float, default=2e-4)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-seq-len", type=int, default=512)
    ap.add_argument("--num-epochs", type=int, default=1)
    ap.add_argument("--max-steps", type=int, default=0)
    ap.add_argument("--lora-r", type=int, default=8)
    ap.add_argument("--lora-alpha", type=int, default=16)
    ap.add_argument("--lora-targets", default="q,k,v,o")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dtype", default="")
    args = ap.parse_args(argv)

    cfg = TrainConfig(
        model=args.model, method=args.method, data_dir=args.data_dir,
        output_dir=args.output_dir, learning_rate=args.learning_rate,
        batch_size=args.batch_size, max_seq_len=args.max_seq_len,
        num_epochs=args.num_epochs, max_steps=args.max_steps,
        checkpoint_every=args.checkpoint_every, seed=args.seed,
        lora=LoraConfig(r=args.lora_r, alpha=args.lora_alpha,
                        targets=tuple(t for t in args.lora_targets.split(",") if t)))
    if args.dtype:
        cfg.dtype = args.dtype
    if args.config_file:
        import yaml

        with open(args.config_file) as f:
            overrides = (yaml.safe_load(f) or {}).get("training", {})
        for k, v in overrides.items():
            k = k.replace("-", "_")
            if hasattr(cfg, k):
                setattr(cfg, k, v)
    return cfg


def main(argv=None):
    from kaito_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    logging.basicConfig(level=logging.INFO)
    cfg = parse_args(argv)
    import jax

    if jax.devices()[0].platform not in ("cpu",) and not cfg.dtype:
        cfg.dtype = "bfloat16"
    result = Trainer(cfg).train()
    logging.info("training complete: %s", result)


if __name__ == "__main__":
    main()
