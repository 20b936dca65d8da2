#!/usr/bin/env python3
"""The server child: the program's own entry point, with the cell's
configuration registered under its name.

Builds the model's metadata from the configuration file's HF-keyed
``config`` with the program's preset generator, registers it with
``hf_id`` pointing at the benchmark's tokenizer directory (which
``load_tokenizer`` accepts as a local path), writes the run's
``--kaito-config-file`` and calls ``kaito_tpu.engine.server.main``,
the entry the pod runs.  No file of the program is changed.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True, help="kbench configuration file")
    ap.add_argument("--name", required=True)
    ap.add_argument("--tokenizer-dir", required=True)
    ap.add_argument("--weight-seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--work-dir", required=True)
    args = ap.parse_args()

    sys.path.insert(0, ROOT)
    from kaito_tpu.models.autogen import metadata_from_hf_config
    from kaito_tpu.models.registry import register_model

    with open(args.config) as f:
        cfg = json.load(f)
    register_model(metadata_from_hf_config(
        args.tokenizer_dir, cfg["config"], name=args.name), replace=True)

    server = cfg["server"]
    kaito_cfg = dict(server.get("config_file", {}), seed=args.weight_seed)
    path = os.path.join(args.work_dir, "kaito-config.yaml")
    with open(path, "w") as f:
        json.dump({"engine": kaito_cfg}, f)       # JSON is YAML
    argv = ["--model", args.name, "--host", "127.0.0.1",
            "--port", str(args.port), "--kaito-config-file", path]
    for flag, value in server.get("args", {}).items():
        argv.append("--" + flag)
        if value is not True:                     # true marks a bare flag
            argv.append(str(value))

    from kaito_tpu.engine import server as program

    program.main(argv)


if __name__ == "__main__":
    main()
