#!/usr/bin/env python3
"""The reference child: expectations for a list of token sequences.

Runs alone on the device (the server is not up).  Makes the weights
the server made — ``TransformerLM.init_params(PRNGKey(seed))`` in the
served type, which is data generation; no ``apply`` path of the
program is called — spreads them over the local devices by their
first axis so that a model too large for one chip fits, and runs the
``forward`` of the reference file the configuration names, loaded as
a module by its path, on the first device.

    run_reference.py <job.json> <out.json>

job: ``reference`` (the file), ``config`` (HF keys), ``weight_seed``,
``platform`` (the one the server was held to: any other is an error,
never a fallback), ``dtype`` ("" = the platform's serving default),
``perturb`` (one of the module's ``PERTURBATIONS``, or ""),
``requests`` (each ``tokens`` and ``start``).  out: per request
``target``, ``top`` and the ``platform`` it was computed on.
"""

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_reference(path: str):
    """The reference file as a module, by its path: it need not lie
    beside this file, and nothing here knows its name."""
    stem = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location("kbench_reference_" + stem,
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main() -> int:
    with open(sys.argv[1]) as f:
        job = json.load(f)
    sys.path.insert(0, ROOT)
    from kaito_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    reference = load_reference(job["reference"])
    perturb = job.get("perturb", "")
    if perturb and perturb not in reference.PERTURBATIONS:
        print(f"{job['reference']} knows no perturbation {perturb!r}, only "
              f"{list(reference.PERTURBATIONS)}", file=sys.stderr)
        return 1
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from kaito_tpu.engine.model import TransformerLM
    from kaito_tpu.models.autogen import arch_from_hf_config

    devs = jax.local_devices()
    if devs[0].platform != job["platform"]:
        print(f"the reference found platform {devs[0].platform!r}, the "
              f"server was held to {job['platform']!r}", file=sys.stderr)
        return 1
    on_cpu = devs[0].platform == "cpu"
    if on_cpu:
        devs = devs[:1]
    dtype = job.get("dtype") or ("float32" if on_cpu else "bfloat16")
    model = TransformerLM(arch_from_hf_config(job["config"]),
                          dtype=jnp.dtype(dtype))
    mesh = Mesh(np.array(devs), ("d",))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    spread = jax.tree.map(
        lambda s: NamedSharding(mesh, P("d") if s.shape[0] % len(devs) == 0
                                else P()), shapes)
    params = jax.jit(model.init_params, out_shardings=spread)(
        jax.random.PRNGKey(job["weight_seed"]))

    def put(tree):
        return jax.device_put(tree, devs[0])

    out = []
    for req in job["requests"]:
        res = reference.forward(job["config"], params, req["tokens"],
                                req["start"], put=put, perturb=perturb)
        out.append(dict({k: [float(x) for x in np.asarray(v)]
                         for k, v in res.items()},
                        platform=devs[0].platform, dtype=dtype))
    with open(sys.argv[2], "w") as f:
        json.dump({"results": out}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
