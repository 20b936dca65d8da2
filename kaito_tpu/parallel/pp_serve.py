"""Pipeline-parallel serving executor (shard_map + ppermute).

The serving side of the planner's tier 3 (``pipeline`` mesh axis) — the
TPU-native counterpart of the reference's multi-node vLLM serving
(``--pipeline-parallel-size`` + Ray executor,
/root/reference/pkg/model/interface.go:519-560).  Where the reference
splits layers across node boundaries and lets Ray drive per-stage
processes, here the model's scanned layer stack reshapes to
``[S, L/S, ...]`` and shards over the pipeline axis of one jitted SPMD
program; the paged KV cache shards the same way, so every stage owns
the KV pages for its own layers and no KV ever crosses a stage
boundary — only the [mb, 1, E] activations move, via ``ppermute``.

Decode runs the GPipe schedule: the decode batch splits into M
microbatches that stream through the stage ring in M + S - 1 ticks, so
at steady state every stage computes a different microbatch.  Prefill
flows one request through the ring (a single-request prefill is
inherently sequential; stages overlap across *ticks* instead).

TP composes *inside* each stage (the reference's tier 3 is exactly
TP-within-node × PP-across-nodes, interface.go:514-530): the mesh
carries a ``tensor`` axis alongside ``pipeline``, the staged weights
keep their Megatron shardings (SERVE_RULES) on that axis, and the
shard_map is *partial-manual* — only the pipeline axis is manual
(explicit ``ppermute`` ring); the tensor axis stays auto, so GSPMD
inserts the TP collectives inside each stage exactly as it does for
the flat-TP engine.

EP composes inside each stage the same way TP does (the expert axis
stays auto, so each stage's expert stacks place over its own devices),
and per-request LoRA stacks split alongside the layer stacks (no
merge-into-base under PP).

Scope: homogeneous single-group layer stacks (no MLA), global
attention (no sliding-window scan flags).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from kaito_tpu.engine.kv_cache import KVCache
from kaito_tpu.engine.model import TransformerLM
from kaito_tpu.parallel.pipeline import split_stage_params


class PipelineServeExecutor:
    """Builds stage-sharded decode/prefill step functions for the engine."""

    def __init__(self, model: TransformerLM, mesh: Mesh,
                 num_microbatches: int = 4, axis: str = "pipeline"):
        if model.is_mla:
            raise ValueError("pipeline-parallel serving does not cover "
                             "MLA models yet")
        if model.arch.sliding_window:
            raise ValueError("pipeline-parallel serving v1 does not cover "
                             "sliding-window attention")
        if len(model.groups) != 1:
            raise ValueError(
                "pipeline-parallel serving needs a homogeneous layer "
                f"stack; {model.md_name if hasattr(model, 'md_name') else ''}"
                f" has {len(model.groups)} layer groups")
        self.model = model
        self.mesh = mesh
        self.axis = axis
        self.num_stages = mesh.shape[axis]
        self.tp = int(mesh.shape.get("tensor", 1))
        # EP composes inside each stage exactly like TP: the expert axis
        # stays on the AUTO side of the partial-manual shard_map, so
        # GSPMD places each stage's expert stacks over its own devices
        # (the flat engine's EP, per stage)
        self.ep = int(mesh.shape.get("expert", 1))
        (self.group,) = model.groups
        if model.arch.num_layers % self.num_stages:
            raise ValueError(f"{model.arch.num_layers} layers do not split "
                             f"into {self.num_stages} stages")
        self.num_microbatches = num_microbatches

    # ------------------------------------------------------------------
    # Layout
    # ------------------------------------------------------------------

    def _param_specs(self, staged_params: dict) -> dict:
        """shard_map in_specs: MANUAL axes only.  The stage dim of each
        layer stack is manual over the pipeline axis; everything else is
        unconstrained here — tensor sharding rides the arrays' own
        placements through the auto axis."""
        gname = self.group.name
        return {
            k: (jax.tree.map(lambda _: P(self.axis), v)
                if k in (gname, "serve_lora")
                else jax.tree.map(lambda _: P(), v))
            for k, v in staged_params.items()
        }

    def _placement_shardings(self, staged_params: dict) -> dict:
        """device_put shardings: pipeline on the stage dim AND the
        Megatron tensor axes from SERVE_RULES on the weight dims, so the
        auto (GSPMD) side of the partial-manual shard_map sees the same
        TP layout the flat-TP engine uses."""
        from kaito_tpu.parallel.sharding import SERVE_RULES

        gname = self.group.name
        axes = self.model.param_logical_axes()

        def leaf(ax, prefix=()):
            if self.tp * self.ep <= 1:
                return NamedSharding(
                    self.mesh, P(*prefix) if prefix else P())
            return NamedSharding(
                self.mesh, P(*prefix, *tuple(SERVE_RULES.spec(ax))))

        def entry(name, v, ax_tree, prefix=()):
            from kaito_tpu.engine.quant import (qtensor_kind,
                                                qtensor_logical_axes)

            ax = ax_tree[name]
            if isinstance(v, dict):     # QTensor {"q8"|"q4", "scale"}
                return {kk: leaf(aa, prefix)
                        for kk, aa in qtensor_logical_axes(
                            ax, qtensor_kind(v) or "int8").items()}
            return leaf(ax, prefix)

        out = {}
        for k, v in staged_params.items():
            if k == gname:
                out[k] = {name: entry(name, sub, axes[gname],
                                      prefix=(self.axis,))
                          for name, sub in v.items()}
            elif k == "serve_lora":
                # adapter factors: stage dim on pipeline, tiny factor
                # dims replicated (same as the flat engine's P() layout)
                out[k] = jax.tree.map(
                    lambda _: NamedSharding(self.mesh, P(self.axis)), v)
            elif k in axes:
                out[k] = entry(k, v, axes)
            else:
                out[k] = jax.tree.map(
                    lambda _: NamedSharding(self.mesh, P()), v)
        return out

    def stage_params(self, params: dict) -> dict:
        """[L, ...] layer stacks -> [S, L/S, ...] sharded over the
        pipeline axis (and the tensor axis per SERVE_RULES); top-level
        params keep their TP sharding and replicate over pipeline."""
        staged = split_stage_params(self.model, params, self.num_stages)
        return jax.device_put(staged, self._placement_shardings(staged))

    def stage_cache(self, cache: KVCache) -> KVCache:
        """[L, pages, ps, H, D] -> [S, L/S, pages, ps, H, D] sharded over
        the pipeline axis (each stage owns its layers' KV), with the
        kv-head dim on tensor when it divides (same rule as the flat-TP
        engine's _cache_sharding)."""
        S = self.num_stages
        spec = [self.axis, None, None, None, None, None]
        if self.tp > 1 and self.model.arch.kv_cache_heads > 1 \
                and self.model.arch.kv_cache_heads % self.tp == 0:
            spec[4] = "tensor"
        sh = NamedSharding(self.mesh, P(*spec))

        def split(a):
            return jax.device_put(
                a.reshape((S, a.shape[0] // S) + a.shape[1:]), sh)

        return KVCache(k=split(cache.k), v=split(cache.v))

    def _local_view(self, params: dict, ck, cv):
        """Inside shard_map: strip the stage dim from this stage's shard."""
        gname = self.group.name
        local_params = {**params,
                        gname: jax.tree.map(lambda v: v[0], params[gname])}
        if "serve_lora" in params:
            local_params["serve_lora"] = jax.tree.map(
                lambda v: v[0], params["serve_lora"])
        return local_params, ck[0], cv[0]

    # ------------------------------------------------------------------
    # Decode (GPipe microbatching)
    # ------------------------------------------------------------------

    def build_decode_fn(self):
        model, axis = self.model, self.axis
        S, M = self.num_stages, self.num_microbatches
        E = model.arch.hidden_size
        V = model.arch.vocab_size
        fwd = [(i, (i + 1) % S) for i in range(S)]

        def local_decode(params, ck, cv, tokens, positions, page_tables,
                         active, adapter_ids):
            p = jax.lax.axis_index(axis)
            local_params, ck_l, cv_l = self._local_view(params, ck, cv)
            B = tokens.shape[0]
            mb = B // M
            pos = positions.reshape(M, mb)
            pts = page_tables.reshape(M, mb, -1)
            act = active.reshape(M, mb)
            aids = adapter_ids.reshape(M, mb)
            # embed once per microbatch (only stage 0 consumes it; the
            # gather is cheap enough to not gate on p == 0)
            x0_all = model._embed(local_params,
                                  tokens.reshape(M, mb)[:, :, None])

            def tick(carry, t):
                recv, ck_l, cv_l, acc = carry
                i_rel = t - p
                valid = (i_rel >= 0) & (i_rel < M)
                i = jnp.clip(i_rel, 0, M - 1)
                x_in = jnp.where(p == 0, x0_all[i], recv)
                cache_l = KVCache(k=ck_l, v=cv_l)
                # invalid (warm-up/drain) ticks mask active so their
                # garbage KV lands on the null page
                x_out, cache_l = model._run_layers(
                    local_params, cache_l, x_in, "decode",
                    positions=pos[i][:, None], page_tables=pts[i],
                    lengths=pos[i] + 1, true_lens=None,
                    active=act[i] & valid, adapter_ids=aids[i])
                ck_l, cv_l = cache_l.k, cache_l.v
                # final-norm + vocab projection only on the last stage's
                # valid ticks — everywhere else the accumulator stays 0
                use = valid & (p == S - 1)
                lg = jax.lax.cond(
                    use,
                    lambda x: model._logits(
                        local_params,
                        model._norm(x, local_params, "final_norm")[:, 0]
                    ).astype(jnp.float32),
                    lambda x: jnp.zeros((mb, V), jnp.float32),
                    x_out)
                acc = acc.at[i].set(jnp.where(use, lg, acc[i]))
                sent = jax.lax.ppermute(x_out, axis, fwd)
                return (sent, ck_l, cv_l, acc), None

            recv0 = jnp.zeros((mb, 1, E), model.dtype)
            acc0 = jnp.zeros((M, mb, V), jnp.float32)
            (_, ck_l, cv_l, acc), _ = jax.lax.scan(
                tick, (recv0, ck_l, cv_l, acc0), jnp.arange(S + M - 1))
            # only the last stage wrote logits; psum replicates them
            logits = jax.lax.psum(acc, axis)
            return ck_l[None], cv_l[None], logits.reshape(B, V)

        ax = self.axis
        sharded = None

        def decode(params, cache, tokens, positions, page_tables, active,
                   adapter_ids=None):
            nonlocal sharded
            if sharded is None:
                specs = self._param_specs(params)
                sharded = jax.shard_map(
                    local_decode, mesh=self.mesh,
                    in_specs=(specs, P(ax), P(ax), P(), P(), P(), P(), P()),
                    out_specs=(P(ax), P(ax), P()),
                    axis_names={ax}, check_vma=False)
            if adapter_ids is None:
                adapter_ids = jnp.zeros(tokens.shape[:1], jnp.int32)
            k, v, logits = sharded(params, cache.k, cache.v, tokens,
                                   positions, page_tables, active,
                                   adapter_ids)
            return KVCache(k=k, v=v), logits

        return decode

    # ------------------------------------------------------------------
    # Prefill (one request through the ring)
    # ------------------------------------------------------------------

    def build_prefill_fn(self, with_context: bool):
        model, axis = self.model, self.axis
        S = self.num_stages
        E = model.arch.hidden_size
        V = model.arch.vocab_size
        fwd = [(i, (i + 1) % S) for i in range(S)]

        def local_prefill(params, ck, cv, tokens, true_lens, page_tables,
                          start_pos, adapter_ids):
            p = jax.lax.axis_index(axis)
            local_params, ck_l, cv_l = self._local_view(params, ck, cv)
            B, T = tokens.shape
            rel = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
            positions = rel + start_pos[:, None] if with_context else rel
            x0 = model._embed(local_params, tokens)

            def tick(carry, t):
                recv, ck_l, cv_l, acc = carry
                valid = t == p               # stage p's turn at tick p
                x_in = jnp.where(p == 0, x0, recv)
                # inactive ticks zero true_lens: garbage KV -> null page
                tl = jnp.where(valid, true_lens, 0)
                cache_l = KVCache(k=ck_l, v=cv_l)
                x_out, cache_l = model._run_layers(
                    local_params, cache_l, x_in, "prefill",
                    positions=positions, page_tables=page_tables,
                    lengths=tl, true_lens=tl, active=None,
                    start_pos=start_pos if with_context else None,
                    adapter_ids=adapter_ids)
                ck_l, cv_l = cache_l.k, cache_l.v
                use = valid & (p == S - 1)

                def final(x):
                    h = model._norm(x, local_params, "final_norm")
                    last = jnp.take_along_axis(
                        h, jnp.maximum(true_lens - 1, 0)[:, None, None]
                        .astype(jnp.int32), axis=1)[:, 0]
                    return model._logits(local_params,
                                         last).astype(jnp.float32)

                lg = jax.lax.cond(
                    use, final, lambda x: jnp.zeros((B, V), jnp.float32),
                    x_out)
                acc = jnp.where(use, lg, acc)
                sent = jax.lax.ppermute(x_out, axis, fwd)
                return (sent, ck_l, cv_l, acc), None

            recv0 = jnp.zeros((B, T, E), model.dtype)
            acc0 = jnp.zeros((B, V), jnp.float32)
            (_, ck_l, cv_l, acc), _ = jax.lax.scan(
                tick, (recv0, ck_l, cv_l, acc0), jnp.arange(S))
            logits = jax.lax.psum(acc, axis)
            return ck_l[None], cv_l[None], logits

        ax = self.axis
        sharded = None

        def prefill(params, cache, tokens, true_lens, page_tables,
                    start_pos=None, adapter_ids=None):
            nonlocal sharded
            if sharded is None:
                specs = self._param_specs(params)
                sharded = jax.shard_map(
                    local_prefill, mesh=self.mesh,
                    in_specs=(specs, P(ax), P(ax), P(), P(), P(), P(), P()),
                    out_specs=(P(ax), P(ax), P()),
                    axis_names={ax}, check_vma=False)
            if start_pos is None:
                start_pos = jnp.zeros((tokens.shape[0],), jnp.int32)
            if adapter_ids is None:
                adapter_ids = jnp.zeros((tokens.shape[0],), jnp.int32)
            k, v, logits = sharded(params, cache.k, cache.v, tokens,
                                   true_lens, page_tables, start_pos,
                                   adapter_ids)
            return KVCache(k=k, v=v), logits

        return prefill
