#!/usr/bin/env python3
"""The delta rule's prefill scan alone, at a cell's shapes, in its forms.

    python benchmarks/gdn_scan.py [--tokens 4096,1536,2048,3072]
                                  [--heads 30,96,192] [--only NAME,NAME]
                                  [--reps 20] [--profile] [--out DIR]

``olmo-hybrid-7b-d8-long`` prefills one prompt a program of 1,536 to
4,096 rows through six delta-rule layers of 30 heads (96 keys, 192
values); ``ops/gdn.gdn_chunked_scan`` is many small operations inside
and round two loops, which no trace names (the scope ``gdn_scan`` names
no op), so this is where a change to it is timed before a cell is run.
Needs the chip (a time from a CPU says nothing of it; the device is
named in the first line printed).  No benchmark cell runs this.

Variants, ``<inverse>+<carry>/<chunk>`` (``tree`` is
``gdn_chunked_scan`` as the tree has it; the rest are built here from
the same formulas, module docstring of ``ops/gdn.py``):

- inverse ``T = (I + A)^-1``: ``rows`` a row at a time over the whole
  chunk (63 steps at a chunk of 64; the form PR 56 replaced); ``mxu`` in
  blocks of 16 rows, the products as small matrix products; ``lanes``
  the tree's own ``_unit_lower_inverse``: blocks of ``GDN_SUB`` rows,
  the batch (chunks x heads) on lanes, the products on the vector unit.
- carry: ``fused`` a chunk's four products inside the loop over chunks
  (the form PR 56 replaced); ``product`` the chunk's transition
  ``S_C = M S_0 + N`` outside it, one product a step inside, the
  outputs for every chunk at once after (the tree's); ``assoc`` the
  same pairs ``(M, N)`` under ``lax.associative_scan`` (no serial loop;
  three times slower at PR 56, PERF.md section 6).
- chunk: 64 (``rows+fused/64`` is the baseline, the parent of PR 56) or
  128 (the tree's).

Every variant is held to ``gdn_recurrence`` before it is timed (exit 1
if one is off by more than ``TOL`` of the largest entry).  A row is one
JSON line: ``ms`` the host's clock round ``--reps`` calls after a warm
one, ``err_o`` / ``err_s`` the largest error of the output and of the
final state.  ``--profile`` traces each variant's calls and adds
``ms_by_scope``: the device's self time a call of the operations under
each phase's scope (``pairs``: the chunk's pairwise products and
decays; ``inverse``; ``wu``: W, U and the carry's operands; ``carry``:
the loop over chunks; ``out``: what follows it).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from kaito_tpu.engine.ops import gdn as G

_HI = jax.lax.Precision.HIGHEST
# of the largest entry of what the recurrence gives: float32 products at
# HIGHEST over 64 chunks read 1e-5 of it (PERF.md section 6, PR 56)
TOL = 1e-4
SCOPES = ("pairs", "inverse", "wu", "carry", "out")


def inverse_rows(A):
    """Row i of the inverse is ``e_i - A[i, :i] T[:i]``."""
    C = A.shape[-1]
    eye = jnp.broadcast_to(jnp.eye(C, dtype=A.dtype), A.shape)

    def row(i, Tm):
        a_i = jax.lax.dynamic_index_in_dim(A, i, axis=-2, keepdims=True)
        e_i = jax.lax.dynamic_index_in_dim(eye, i, axis=-2, keepdims=True)
        new = e_i - jnp.einsum("...ij,...jk->...ik", a_i, Tm, precision=_HI)
        return jax.lax.dynamic_update_index_in_dim(Tm, new, i, axis=-2)

    return jax.lax.fori_loop(1, C, row, eye)


def inverse_mxu(A, sub=16):
    """Diagonal blocks by the row recurrence, all at once; the blocks
    under them a block row at a time, ``-T_bb (A[b, :b] T[:b, :b])``."""
    *lead, C, _ = A.shape
    nb = C // sub
    Ad = jnp.stack([A[..., n * sub:(n + 1) * sub, n * sub:(n + 1) * sub]
                    for n in range(nb)], axis=-3)
    Td = inverse_rows(Ad)
    Tm = Td[..., 0, :, :]
    for n in range(1, nb):
        below = -jnp.einsum(
            "...ij,...jk,...kl->...il", Td[..., n, :, :],
            A[..., n * sub:(n + 1) * sub, :n * sub], Tm, precision=_HI)
        Tm = jnp.concatenate([
            jnp.pad(Tm, [(0, 0)] * len(lead) + [(0, 0), (0, sub)]),
            jnp.concatenate([below, Td[..., n, :, :]], axis=-1)], axis=-2)
    return Tm


# "lanes" is the tree's own: blocks of GDN_SUB rows, the batch on lanes
INVERSES = {"rows": inverse_rows, "mxu": inverse_mxu,
            "lanes": G._unit_lower_inverse}


def scan(q, k, v, g, beta, s0, inverse="rows", carry="fused", chunk=64):
    """``ops/gdn._chunked_scan``'s formulas with the inverse and the
    carry to choose; T a whole number of chunks."""
    b, T, H, dk = q.shape
    dv = v.shape[-1]
    C = chunk
    nc = T // C
    assert nc * C == T, (T, chunk)
    ein = lambda spec, x, y: jnp.einsum(spec, x, y, precision=_HI)
    with jax.named_scope("pairs"):
        q, k, v = (jnp.moveaxis(x.reshape(b, nc, C, H, x.shape[-1]), 3, 2)
                   for x in (q, k, v))
        g, beta = (jnp.moveaxis(x.reshape(b, nc, C, H), 3, 2)
                   for x in (g, beta))
        gamma = jnp.cumsum(g, axis=-1)
        ci = jnp.arange(C)
        seg = gamma[..., :, None] - gamma[..., None, :]
        decay = jnp.exp(jnp.where(ci[:, None] >= ci[None, :], seg, -jnp.inf))
        kk = ein("bchid,bchjd->bchij", k, k)
        A = jnp.where(ci[:, None] > ci[None, :],
                      beta[..., :, None] * kk * decay, 0.0)
        qk = ein("bchid,bchjd->bchij", q, k) * decay
    with jax.named_scope("inverse"):
        Tm = INVERSES[inverse](A)
    with jax.named_scope("wu"):
        eg = jnp.exp(gamma)[..., None]
        W = ein("bchij,bchjd->bchid", Tm, beta[..., None] * k * eg)
        U = ein("bchij,bchjd->bchid", Tm, beta[..., None] * v)
        q_in = q * eg
        k_end = k * jnp.exp(gamma[..., -1:] - gamma)[..., None]
        chunk_decay = jnp.exp(gamma[..., -1])                # [b,nc,H]
        if carry != "fused":
            M = jnp.eye(dk, dtype=jnp.float32) \
                * chunk_decay[..., None, None] \
                - ein("bchjk,bchjd->bchkd", k_end, W)
            N = ein("bchjk,bchjv->bchkv", k_end, U)

    if carry == "fused":
        def step(S, inp):
            W_c, U_c, qk_c, qin_c, kend_c, d_c = inp
            v_new = U_c - ein("bhik,bhkv->bhiv", W_c, S)
            o = ein("bhik,bhkv->bhiv", qin_c, S) \
                + ein("bhij,bhjv->bhiv", qk_c, v_new)
            S = S * d_c[..., None, None] \
                + ein("bhjk,bhjv->bhkv", kend_c, v_new)
            return S, o

        with jax.named_scope("carry"):
            s_last, o = jax.lax.scan(
                step, s0, tuple(jnp.moveaxis(x, 1, 0) for x in
                                (W, U, qk, q_in, k_end, chunk_decay)))
        with jax.named_scope("out"):
            o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)
            return o.reshape(b, T, H, dv), s_last

    with jax.named_scope("carry"):
        if carry == "product":
            def step(S, inp):
                M_c, N_c = inp
                return ein("bhkj,bhjv->bhkv", M_c, S) + N_c, S

            s_last, starts = jax.lax.scan(
                step, s0, (jnp.moveaxis(M, 1, 0), jnp.moveaxis(N, 1, 0)))
            starts = jnp.moveaxis(starts, 0, 1)              # [b,nc,H,dk,dv]
        else:
            # s0 goes into the first chunk's sum; then
            # (M2, N2) o (M1, N1) = (M2 M1, M2 N1 + N2)
            N = N.at[:, 0].add(ein("bhkj,bhjv->bhkv", M[:, 0], s0))

            def compose(first, second):
                M1, N1 = first
                M2, N2 = second
                return (ein("bchkj,bchjd->bchkd", M2, M1),
                        ein("bchkj,bchjv->bchkv", M2, N1) + N2)

            _, ends = jax.lax.associative_scan(compose, (M, N), axis=1)
            s_last = ends[:, -1]
            starts = jnp.concatenate([s0[:, None], ends[:, :-1]], axis=1)
    with jax.named_scope("out"):
        v_new = U - ein("bchik,bchkv->bchiv", W, starts)
        o = ein("bchik,bchkv->bchiv", q_in, starts) \
            + ein("bchij,bchjv->bchiv", qk, v_new)
        return jnp.moveaxis(o, 2, 3).reshape(b, T, H, dv), s_last


def case(T, H, dk, dv, seed=0):
    """One prompt of T tokens as a layer hands it over: q and k
    normalised, decays from a thousandth to 1.6 a token, beta in
    (0, 2), a state that is not zero."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (1, T, H, dk))
    k = jax.random.normal(ks[1], (1, T, H, dk))
    v = jax.random.normal(ks[2], (1, T, H, dv))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.exp(jax.random.uniform(ks[3], (1, T, H), minval=np.log(1e-3),
                                    maxval=np.log(1.6)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (1, T, H)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (1, H, dk, dv))


FORMS = [("rows", "fused", 64), ("mxu", "fused", 64), ("lanes", "fused", 64),
         ("rows", "product", 64), ("lanes", "product", 64),
         ("lanes", "fused", 128), ("lanes", "product", 128),
         ("mxu", "product", 128), ("lanes", "assoc", 128)]


def variants():
    out = {"tree": G.gdn_chunked_scan}
    for inv, carry, chunk in FORMS:
        out[f"{inv}+{carry}/{chunk}"] = (
            lambda *a, inv=inv, carry=carry, chunk=chunk:
            scan(*a, inverse=inv, carry=carry, chunk=chunk))
    return out


def _scope_of_ops(hlo: str) -> dict:
    """{instruction name: the first of SCOPES in its op_name} from
    optimized HLO text (a fusion carries its root's metadata)."""
    out = {}
    for m in re.finditer(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?"
                         r'op_name="([^"]*)"', hlo, re.M):
        found = [s for s in SCOPES if f"/{s}/" in m.group(2) + "/"]
        out[m.group(1)] = found[0] if found else "other"
    return out


def ms_by_scope(compiled, args, reps: int) -> dict:
    """Trace ``reps`` calls; the device's self time a call by scope."""
    sys.path.insert(0, os.path.join(ROOT, "kbench"))
    import trace_reduce

    scope = _scope_of_ops(compiled.as_text())
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(reps):
            out = compiled(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        pb = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        ops = trace_reduce.reduce(pb[0])["ops"]
    by = {}
    for name, sec in ops.items():
        s = scope.get(name.split("/", 1)[1].lstrip("%"), "other")
        by[s] = by.get(s, 0.0) + sec * 1e3 / reps
    return {s: round(ms, 3) for s, ms in sorted(by.items())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", default="4096,1536,2048,3072")
    ap.add_argument("--heads", default="30,96,192",
                    help="heads, keys, values a head")
    ap.add_argument("--only", default="")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--expect-platform", default="tpu",
                    help="what to refuse to run without (cpu: a rehearsal)")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "gdn_scan"))
    args = ap.parse_args()
    H, dk, dv = (int(x) for x in args.heads.split(","))
    only = [x for x in args.only.split(",") if x]
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}),
          flush=True)
    if dev.platform != args.expect_platform:
        print(f"expected a {args.expect_platform}, not a {dev.platform}",
              file=sys.stderr)
        return 1
    rows, bad = [], 0
    for T in (int(x) for x in args.tokens.split(",")):
        inputs = case(T, H, dk, dv)
        want_o, want_s = jax.jit(G.gdn_recurrence)(*inputs)
        top_o = float(jnp.abs(want_o).max())
        top_s = float(jnp.abs(want_s).max())
        for name, fn in variants().items():
            if only and name not in only:
                continue
            t = time.perf_counter()
            compiled = jax.jit(fn).lower(*inputs).compile()
            compile_s = time.perf_counter() - t
            o, s = compiled(*inputs)
            err_o = float(jnp.abs(o - want_o).max())
            err_s = float(jnp.abs(s - want_s).max())
            t = time.perf_counter()
            for _ in range(args.reps):
                out = compiled(*inputs)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t) / args.reps * 1e3
            mem = compiled.memory_analysis()
            row = {"T": T, "variant": name, "ms": round(ms, 3),
                   "compile_s": round(compile_s, 2),
                   "temp_mb": round(
                       getattr(mem, "temp_size_in_bytes", 0) / 2 ** 20, 1),
                   "err_o": err_o, "err_s": err_s,
                   "max_o": top_o, "max_s": top_s,
                   "ok": err_o <= TOL * top_o and err_s <= TOL * top_s}
            bad += not row["ok"]
            if args.profile:
                row["ms_by_scope"] = ms_by_scope(compiled, inputs, args.reps)
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "rows.json"), "w") as f:
        json.dump(rows, f, indent=0)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
